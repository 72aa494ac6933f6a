"""Seeded bf16 weights for a served model, made on the device in one call.

The pytree has the program's parameter layout (the architecture module's
``param_layout``, ``bench/archs/``).
Stacked leaves are drawn one layer at a time inside the same program, so
the f32 draw of a layer is the only transient beside the bf16 result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import arch


def seed_words(seed: int, salt: int) -> np.ndarray:
    """A seed of any size (and a salt) as four uint32 words for the device."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, (seed >> 64) & 0xFFFFFFFF,
                     salt & 0xFFFFFFFF], np.uint32)


def _draw(key, shape, law: str, std: float):
    if law == "normal":
        x = jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32) * std
    elif law == "scale":
        x = jax.random.normal(key, shape, jnp.float32) * std
    elif law == "near_one":
        x = 1.0 + jax.random.normal(key, shape, jnp.float32) * std
    elif law == "A_log":  # A = -exp(A_log) uniform in [-16, -1]
        x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif law == "dt_bias":  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown law {law!r}")
    return x.astype(jnp.bfloat16)


def _leaf(key, shape, law, std, stacked: bool):
    if not stacked or len(shape) < 2:
        return _draw(key, shape, law, std)
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: _draw(k, shape[1:], law, std), keys)


@functools.partial(jax.jit, static_argnums=(0,))
def _make(dims, words):
    key = jax.random.key(0)
    for w in range(words.shape[0]):
        key = jax.random.fold_in(key, words[w])
    layout = arch(dims).param_layout(dims)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(layout, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    out = [
        # Every leaf under "blocks" carries the stacked layers axis first.
        _leaf(k, shape, law, std, stacked=path[0].key == "blocks")
        for k, (path, (shape, law, std)) in zip(keys, leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def make_weights(dims, seed: int, salt: int):
    """bf16 parameter pytree of ``dims`` from ``seed`` (``salt`` tells models apart)."""
    return _make(dims, jax.device_put(seed_words(seed, salt)))
