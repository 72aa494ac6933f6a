"""Jitted public API for batched Eq. 2 utility scoring.

Consumed by the scheduling fast path (repro.core.fastpath) when the
"pallas" utility backend is selected; the numpy expressions in fastpath
remain the default backend and the fallback wherever JAX is unavailable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import for_platform
from repro.kernels.utility.kernel import utility_scores_pallas
from repro.kernels.utility.ref import utility_scores_ref

__all__ = ["utility_scores"]


@functools.partial(jax.jit, static_argnames=("penalty", "use_kernel"))
def utility_scores(
    acc, deadlines, completions, penalty: str = "sigmoid", use_kernel: bool = True,
):
    """(U (R, M), column means (M,)) for one (requests x models) tile.

    ``deadlines`` is (R,); ``completions`` broadcasts to acc's shape —
    pass (M,) for a shared per-variant completion (grouped selection) or
    the full (R, M) matrix."""
    acc = jnp.asarray(acc, jnp.float32)
    e = jnp.broadcast_to(jnp.asarray(completions, jnp.float32), acc.shape)
    d = jnp.asarray(deadlines, jnp.float32)
    if not use_kernel:
        return utility_scores_ref(acc, d, e, penalty)
    u, sums = for_platform(
        functools.partial(utility_scores_pallas, penalty=penalty), acc, d, e)
    return u, sums / acc.shape[0]
