"""Jitted wrapper for flash-decode, model cache layout in/out."""
from __future__ import annotations

import functools

import jax

from repro.kernels import for_platform
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref

__all__ = ["decode_attention"]


@functools.partial(jax.jit, static_argnames=("window", "use_kernel"))
def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     use_kernel: bool = True):
    """Model layout: q (B, 1, Hq, D); caches (B, S, Hkv, D); lengths (B,).

    Returns (B, 1, Hq, D)."""
    b, _, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    qk = q.reshape(b, hkv, g, d)
    kk = k_cache.transpose(0, 2, 1, 3)
    vk = v_cache.transpose(0, 2, 1, 3)
    if use_kernel:
        out = for_platform(
            functools.partial(decode_attention_pallas, window=window), qk, kk, vk, lengths)
    else:
        out = decode_attention_ref(qk, kk, vk, lengths, window=window)
    return out.reshape(b, 1, hq, d)
