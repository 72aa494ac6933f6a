"""Pallas TPU flash-decode kernel: one query token vs a long KV cache.

Layout: q (B, Hkv, G, D);  k_cache, v_cache (B, Hkv, S, D);  lengths (B,)
valid-position counts.  Grid (B, Hkv, nk): the KV sequence is the
streamed dimension (split-KV), with the online-softmax carry in VMEM —
on TPU this is the memory-bound roofline case: the kernel's work is
streaming K/V at HBM bandwidth; the G query rows ride along in VMEM.

G (q heads per kv head) is padded to 8 sublanes so the (G, block_k)
score tile is layout-legal on the VPU; D and block_k stay multiples of
128 lanes for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_pallas"]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_k, nk, window):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[0]  # valid positions in this row's cache
    k_lo = ik * block_k
    lo_bound = length - window if window > 0 else 0

    @pl.when(jnp.logical_and(k_lo < length, k_lo + block_k > lo_bound))
    def _step():
        q = q_ref[0, 0, :, :]  # (G, D)
        k = k_ref[0, 0, :, :]  # (block_k, D)
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, block_k)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window > 0:
            mask &= k_pos >= length - window
        s = jnp.where(mask, s, _NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(
    q, k_cache, v_cache, lengths, *, window: int = 0,
    block_k: int = 256, scale: float | None = None, interpret: bool,
):
    """q: (B, Hkv, G, D);  k/v_cache: (B, Hkv, S, D);  lengths: (B,) int32.

    Returns (B, Hkv, G, D)."""
    b, hkv, g, d = q.shape
    _, _, s, _ = k_cache.shape
    scale = scale if scale is not None else d ** -0.5
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (s + pad) // block_k

    kernel = functools.partial(_kernel, scale=scale, block_k=block_k, nk=nk, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ik: (bi,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, ik: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ik: (bi, hi, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bi, hi, ik: (bi, hi, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, ik: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k_cache, v_cache)
    return out
