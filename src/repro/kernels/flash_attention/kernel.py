"""Pallas TPU flash-attention (prefill) kernel.

Layout: q (B, Hkv, G, Sq, D);  k, v (B, Hkv, Skv, D) — GQA-native (no KV
head replication in HBM).  Grid (B*Hkv, G, nq, nk); the online-softmax
state (m, l, acc) lives in VMEM scratch and is carried across the nk
grid dimension (TPU grids iterate minor-most last, sequentially per
core, which is what makes the carry valid).

Causal + sliding-window masking is positional; fully-masked (q, k) block
pairs are skipped with ``pl.when`` (no MXU work issued), so the kernel
does the true causal/banded FLOPs.

Block sizes default to (128, 128): MXU-aligned (128 lanes), and the VMEM
working set per step is q(128xD) + k/v(128xD) + scores(128x128 fp32) +
acc(128xD fp32) ~ 0.5 MB at D=256 — far under the ~16 MB VMEM budget,
leaving room for Mosaic's double buffering of the k/v streams.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, block_q, block_k, nk, seq_q, seq_k, causal, window):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * block_q + (seq_k - seq_q)  # absolute position of first query
    k_lo = ik * block_k

    # Block-level skip: entirely above the causal diagonal / left of band.
    run = True
    if causal:
        run = k_lo <= q_lo + block_q - 1
    if window > 0:
        run = jnp.logical_and(run, k_lo + block_k - 1 > q_lo - window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0, :, :]  # (block_q, D)
        k = k_ref[0, :, :]  # (block_k, D)
        v = v_ref[0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (k_pos < seq_k) & (q_pos < seq_k)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
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


def flash_attention_pallas(
    q, k, v, *, causal: bool = True, window: int = 0,
    block_q: int = 128, block_k: int = 128, scale: float | None = None,
    interpret: bool,
):
    """q: (B, Hkv, G, Sq, D);  k, v: (B, Hkv, Skv, D) -> (B, Hkv, G, Sq, D).

    ``interpret=True`` runs the kernel body on the CPU for validation;
    ``repro.kernels.for_platform`` picks it from the lowering platform.
    """
    b, hkv, g, sq, d = q.shape
    _, _, skv, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq_p, skv_p = sq + pad_q, skv + pad_k
    nq, nk = sq_p // block_q, skv_p // block_k

    bh = b * hkv
    qr = q.reshape(bh, g, sq_p, d)
    kr = k.reshape(bh, skv_p, d)
    vr = v.reshape(bh, skv_p, d)

    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k, nk=nk,
        seq_q=sq, seq_k=skv, causal=causal, window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bhi, gi, iq, ik: (bhi, gi, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bhi, gi, iq, ik: (bhi, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bhi, gi, iq, ik: (bhi, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda bhi, gi, iq, ik: (bhi, gi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, g, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(b, hkv, g, sq_p, d)
    return out[:, :, :, :sq, :]
