"""Pallas TPU kernel: batched Eq. 2 utility scoring + Eq. 13 reduction.

The scheduling fast path scores whole (requests x models) tiles at once:

    U[r, m] = A[r, m] * (1 - clip(gamma_a(d_r, e[r, m]), 0, 1))     (Eq. 2)

and group-level selection (Eq. 13) needs the column means of U.  Both are
fused here: the grid walks request-row blocks, each step evaluates the
penalty + utility tile on the VPU and accumulates masked column sums in
VMEM scratch, emitting the final sums on the last step.  The penalty is a
static kernel parameter, so each variant compiles to straight-line
where-chains (no gather, no control flow).

Window matrices are tiny by kernel standards (R <= a few thousand, M <=
~8 padded to one 128-lane tile), so this is bandwidth-trivial — the point
is keeping the whole scoring step on-device next to the Eq. 9 matmul when
windows are batched (ROADMAP: JIT-compiled multi-window scheduling).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.utility.ref import gamma

__all__ = ["utility_scores_pallas"]


def _kernel(acc_ref, d_ref, e_ref, u_ref, sum_ref, acc_scr, *, penalty, nr, block_r, n_rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    a = acc_ref[...]  # (block_r, Mp)
    d = d_ref[...]  # (block_r, 1)
    e = e_ref[...]  # (block_r, Mp)
    g = gamma(penalty, d, e)
    u = a * (1.0 - jnp.clip(g, 0.0, 1.0))
    u_ref[...] = u

    # Masked Eq. 13 column sums: padding rows must not shift group means.
    row = i * block_r + jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)
    acc_scr[...] += jnp.sum(jnp.where(row < n_rows, u, 0.0), axis=0, keepdims=True)

    @pl.when(i == nr - 1)
    def _done():
        sum_ref[...] = acc_scr[...]


def utility_scores_pallas(
    acc, deadlines, completions, penalty: str = "sigmoid",
    block_r: int = 128, *, interpret: bool,
):
    """acc (R, M); deadlines (R,); completions (R, M).

    Returns (U (R, M) float32, column sums (M,) float32) — divide by R for
    the Eq. 13 column means."""
    acc = jnp.asarray(acc, jnp.float32)
    deadlines = jnp.asarray(deadlines, jnp.float32)
    completions = jnp.asarray(completions, jnp.float32)
    r, m = acc.shape
    block_r = min(block_r, max(r, 8))
    pad_r = (-r) % block_r
    pad_m = (-m) % 128  # one f32 lane tile
    if pad_r or pad_m:
        acc = jnp.pad(acc, ((0, pad_r), (0, pad_m)))
        completions = jnp.pad(completions, ((0, pad_r), (0, pad_m)))
    if pad_r:
        # Padded deadlines stay positive so every penalty branch is benign.
        deadlines = jnp.pad(deadlines, ((0, pad_r),), constant_values=1.0)
    d2 = deadlines[:, None]
    mp = m + pad_m
    nr = (r + pad_r) // block_r

    kernel = functools.partial(
        _kernel, penalty=penalty, nr=nr, block_r=block_r, n_rows=r
    )
    u, sums = pl.pallas_call(
        kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_r, mp), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_r, mp), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, mp), lambda i: (i, 0)),
            pl.BlockSpec((1, mp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r + pad_r, mp), jnp.float32),
            jax.ShapeDtypeStruct((1, mp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, mp), jnp.float32)],
        interpret=interpret,
    )(acc, d2, completions)
    return u[:r, :m], sums[0, :m]
