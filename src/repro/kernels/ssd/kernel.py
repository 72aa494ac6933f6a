"""Pallas TPU SSD (Mamba-2 state-space duality) chunk kernel.

One grid step processes one (batch, chunk) cell: the intra-chunk
quadratic "attention form" plus the inter-chunk state recurrence, with
the running state carried in VMEM scratch across the chunk grid
dimension (TPU grids run sequentially, so the carry is well-defined —
same trick as the flash kernels' online softmax).

Layout (ngroups == 1, mamba2-130m's configuration):
    xdt (B, S, H, P)   inputs pre-multiplied by dt   (ops.py)
    dA  (B, S, H)      dt * A  (negative decays)     (ops.py)
    Bm, Cm (B, S, N)   state in/out projections
    y   (B, S, H, P);  final_state (B, H, P, N)

Per-chunk VMEM working set at (l=128, H=24, P=64, N=128):
    x tile 128x1536 f32 (0.8 MB) + B/C 128x128 + L (24,128,128) f32
    (1.6 MB) + state (24,64,128) f32 (0.8 MB)  ~ 4 MB < VMEM.
The three contractions are h-batched dot_generals (MXU): scores
(l x N @ N x l), y_diag ((l x l) @ (l x P)), state update (N x l @ l x P).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_pallas"]


def _kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, fs_ref, state_scr, *,
            chunk, nheads, headdim, nstate, nc):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0]  # (l, H, P)
    dA = dA_ref[0]  # (l, H)
    bm = b_ref[0]  # (l, N)
    cm = c_ref[0]  # (l, N)

    cum = jnp.cumsum(dA, axis=0)  # (l, H)
    # causal decay matrix L[h, i, j] = exp(cum[i,h] - cum[j,h]) for i >= j
    diff = cum[:, None, :] - cum[None, :, :]  # (l, l, H)
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = (li >= lj)[:, :, None]
    L = jnp.where(causal, jnp.exp(diff), 0.0)  # (l, l, H)

    # scores (shared across heads, g=1): (l, l) = C @ B^T
    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (l_i, l_j)
    w = scores[:, :, None] * L  # (l, l, H)

    # y_diag[h] = w[:, :, h] @ xdt[:, h, :]  — h-batched MXU matmul
    wt = w.transpose(2, 0, 1)  # (H, l, l)
    xt = xdt.transpose(1, 0, 2)  # (H, l, P)
    y_diag = jax.lax.dot_general(
        wt, xt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # (H, l, P)

    # inter-chunk: y_off[h] = decay_out[:, h, None] * (C @ state_prev[h])
    state = state_scr[...]  # (H, P, N)
    cs = jax.lax.dot_general(
        jnp.broadcast_to(cm[None], (nheads, chunk, nstate)), state,
        (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32,
    )  # (H, l, P)
    decay_out = jnp.exp(cum).transpose(1, 0)  # (H, l)
    y = y_diag + cs * decay_out[:, :, None]
    y_ref[0] = y.transpose(1, 0, 2).astype(y_ref.dtype)  # (l, H, P)

    # state update: S' = exp(sum dA) * S + sum_j exp(cum_end - cum_j) B_j xdt_j
    total = cum[-1, :]  # (H,)
    decay_to_end = jnp.exp(total[None, :] - cum)  # (l, H)
    bx = jnp.broadcast_to(bm[None], (nheads, chunk, nstate)) * decay_to_end.transpose(1, 0)[:, :, None]
    new_contrib = jax.lax.dot_general(
        xt, bx, (((1,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # (H, P, N)
    state_scr[...] = jnp.exp(total)[:, None, None] * state + new_contrib

    @pl.when(ic == nc - 1)
    def _done():
        fs_ref[0] = state_scr[...]


def ssd_pallas(xdt, dA, bm, cm, chunk: int = 128, *, interpret: bool):
    """xdt (B,S,H,P) f32; dA (B,S,H) f32; bm, cm (B,S,N) f32 (ngroups=1).

    Returns (y (B,S,H,P) f32, final_state (B,H,P,N) f32)."""
    b, s, h, p = xdt.shape
    n = bm.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} must divide chunk {chunk}")
    nc = s // chunk
    kernel = functools.partial(
        _kernel, chunk=chunk, nheads=h, headdim=p, nstate=n, nc=nc
    )
    y, fs = pl.pallas_call(
        kernel,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, h, p), lambda bi, ic: (bi, ic, 0, 0)),
            pl.BlockSpec((1, chunk, h), lambda bi, ic: (bi, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, ic: (bi, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, ic: (bi, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, h, p), lambda bi, ic: (bi, ic, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda bi, ic: (bi, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((h, p, n), jnp.float32)],
        interpret=interpret,
    )(xdt.astype(jnp.float32), dA.astype(jnp.float32),
      bm.astype(jnp.float32), cm.astype(jnp.float32))
    return y, fs
