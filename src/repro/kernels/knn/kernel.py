"""Pallas TPU k-NN kernel: SneakPeek evidence (paper §IV-B).

Computes, for a batch of queries, the k nearest training points (L2) and
their labels — the multinomial-evidence generator that SneakPeek runs
once per request.  This is the paper's own data-path hot spot (they use
Faiss on CPU); on TPU it becomes a tiled distance-matrix streaming
problem that the MXU eats:

    d2(i, j) = |q_i|^2 - 2 q_i . x_j + |x_j|^2

Grid (nq, nn): per (query-block, train-block) compute the (block_q,
block_n) distance tile via one MXU matmul + rank-1 corrections, then
merge into the running top-k held in VMEM scratch.  The merge is k
rounds of (min, first index of the min, mask) — k is small (<= 16), and
each round is a vectorized VPU reduction over the tile; no sort and no
scatter (neither lowers through Mosaic) is used.  Train-point norms are
precomputed once on-host (ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["knn_pallas"]

_INF = 0.7 * float(jnp.finfo(jnp.float32).max)


def _kernel(q_ref, x_ref, xn_ref, y_ref, dist_ref, label_ref,
            best_d_scr, best_l_scr, *, k, block_n, nn, n_total):
    jn = pl.program_id(1)

    @pl.when(jn == 0)
    def _init():
        best_d_scr[...] = jnp.full_like(best_d_scr, _INF)
        best_l_scr[...] = jnp.zeros_like(best_l_scr)

    q = q_ref[...]  # (block_q, D)
    x = x_ref[...]  # (block_n, D)
    xn = xn_ref[...]  # (1, block_n)
    y = y_ref[...]  # (1, block_n) float32 labels

    # -2 q.x^T on the MXU; |q|^2 is constant per row (dropped — it does not
    # change the ranking); |x|^2 as a rank-1 correction.
    # HIGHEST keeps the f32 product exact enough that the ranking differs
    # from the f32 reference only on rounding-level distance ties.
    d2 = xn - 2.0 * jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (block_q, block_n)
    icol = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where(icol < n_total - jn * block_n, d2, _INF)  # mask padding rows
    col = icol.astype(jnp.float32)
    tile_l = jnp.broadcast_to(y, d2.shape)

    # Merge the tile into the running top-k: k rounds of extract-min over
    # two fixed-shape pools — the tile and the previous best list (whose
    # entries come from earlier blocks, so they win distance ties, exactly
    # the lower-index-first order of lax.top_k).  Every update is an
    # iota-masked select: no scatter, and no shape grows per round.
    old_d = best_d_scr[...]  # (block_q, k), ascending
    old_l = best_l_scr[...]
    kcol = jax.lax.broadcasted_iota(jnp.int32, old_d.shape, 1).astype(jnp.float32)
    new_d = old_d
    new_l = old_l
    for j in range(k):
        tile_min = jnp.min(d2, axis=1, keepdims=True)  # (block_q, 1)
        tile_at = jnp.min(jnp.where(d2 == tile_min, col, float(block_n)),
                          axis=1, keepdims=True)
        tile_hit = col == tile_at
        tile_lab = jnp.sum(jnp.where(tile_hit, tile_l, 0.0), axis=1, keepdims=True)
        old_min = jnp.min(old_d, axis=1, keepdims=True)
        old_at = jnp.min(jnp.where(old_d == old_min, kcol, float(k)),
                         axis=1, keepdims=True)
        old_hit = kcol == old_at
        old_lab = jnp.sum(jnp.where(old_hit, old_l, 0.0), axis=1, keepdims=True)
        take = tile_min < old_min  # (block_q, 1)
        slot = kcol == float(j)
        new_d = jnp.where(slot, jnp.where(take, tile_min, old_min), new_d)
        new_l = jnp.where(slot, jnp.where(take, tile_lab, old_lab), new_l)
        d2 = jnp.where(tile_hit & take, _INF, d2)
        old_d = jnp.where(old_hit & ~take, _INF, old_d)
    best_d_scr[...] = new_d
    best_l_scr[...] = new_l

    @pl.when(jn == nn - 1)
    def _done():
        dist_ref[...] = best_d_scr[...]
        label_ref[...] = best_l_scr[...]


def knn_pallas(queries, train_x, train_norms, train_y, k: int,
               block_q: int = 128, block_n: int = 512, *, interpret: bool):
    """queries (Q, D); train_x (N, D); train_norms (N,); train_y (N,) float32.

    Returns (dists (Q, k), labels (Q, k)) — labels as float32 values.
    NOTE: distances omit the |q|^2 term (ranking-invariant)."""
    qn, d = queries.shape
    n = train_x.shape[0]
    block_q = min(block_q, qn)
    block_n = min(block_n, n)
    pad_q = (-qn) % block_q
    pad_n = (-n) % block_n
    if pad_q:
        queries = jnp.pad(queries, ((0, pad_q), (0, 0)))
    if pad_n:
        train_x = jnp.pad(train_x, ((0, pad_n), (0, 0)))
        train_norms = jnp.pad(train_norms, ((0, pad_n),))
        train_y = jnp.pad(train_y, ((0, pad_n),))
    nq = (qn + pad_q) // block_q
    nn_blocks = (n + pad_n) // block_n

    kernel = functools.partial(_kernel, k=k, block_n=block_n, nn=nn_blocks, n_total=n)
    dists, labels = pl.pallas_call(
        kernel,
        grid=(nq, nn_blocks),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda iq, jn: (iq, 0)),
            pl.BlockSpec((block_n, d), lambda iq, jn: (jn, 0)),
            pl.BlockSpec((1, block_n), lambda iq, jn: (0, jn)),
            pl.BlockSpec((1, block_n), lambda iq, jn: (0, jn)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda iq, jn: (iq, 0)),
            pl.BlockSpec((block_q, k), lambda iq, jn: (iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qn + pad_q, k), jnp.float32),
            jax.ShapeDtypeStruct((qn + pad_q, k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, k), jnp.float32),
            pltpu.VMEM((block_q, k), jnp.float32),
        ],
        interpret=interpret,
    )(queries.astype(jnp.float32), train_x.astype(jnp.float32),
      train_norms.astype(jnp.float32)[None, :], train_y.astype(jnp.float32)[None, :])
    return dists[:qn], labels[:qn]
