"""Jitted public k-NN API used by repro.core.sneakpeek.KNNSneakPeek."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import for_platform
from repro.kernels.knn.kernel import knn_pallas
from repro.kernels.knn.ref import knn_class_votes_ref, knn_ref

__all__ = ["knn_class_votes", "knn_topk"]


@functools.partial(jax.jit, static_argnames=("k", "use_kernel"))
def knn_topk(queries, train_x, train_y, k: int, use_kernel: bool = True):
    queries = jnp.asarray(queries, jnp.float32)
    train_x = jnp.asarray(train_x, jnp.float32)
    train_y = jnp.asarray(train_y)
    if not use_kernel:
        return knn_ref(queries, train_x, train_y, k)
    norms = (train_x**2).sum(axis=1)
    return for_platform(
        functools.partial(knn_pallas, k=k),
        queries, train_x, norms, train_y.astype(jnp.float32),
    )


@functools.partial(jax.jit, static_argnames=("k", "num_classes", "use_kernel"))
def knn_class_votes(queries, train_x, train_y, k: int, num_classes: int,
                    use_kernel: bool = True):
    """(Q, num_classes) k-NN vote counts (SneakPeek evidence)."""
    if not use_kernel:
        return knn_class_votes_ref(
            jnp.asarray(queries, jnp.float32), jnp.asarray(train_x, jnp.float32),
            jnp.asarray(train_y), k, num_classes)
    _, labels = knn_topk(queries, train_x, train_y, k)
    return jax.nn.one_hot(labels.astype(jnp.int32), num_classes).sum(axis=1)
