"""Jitted public wrapper: model-layout in/out, kernel or oracle backend."""
from __future__ import annotations

import functools

import jax

from repro.kernels import for_platform
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref

__all__ = ["flash_attention"]


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_kernel"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    use_kernel: bool = True):
    """Model layout: q (B, Sq, Hq, D); k, v (B, Skv, Hkv, D) -> (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    qk = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4)
    kk = k.transpose(0, 2, 1, 3)
    vk = v.transpose(0, 2, 1, 3)
    if use_kernel:
        out = for_platform(
            functools.partial(flash_attention_pallas, causal=causal, window=window), qk, kk, vk)
    else:
        out = flash_attention_ref(qk, kk, vk, causal=causal, window=window)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
