"""Production mesh construction (defined as functions — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 v5e pod (data, model); 2x16x16 for two pods (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests / examples / elastic restarts).

    Every axis is ``Auto``: the sharding rules here constrain activations
    with ``with_sharding_constraint`` and leave the rest to the
    partitioner, which is the mode ``jax.make_mesh`` no longer defaults
    to (its default is ``Explicit``)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
