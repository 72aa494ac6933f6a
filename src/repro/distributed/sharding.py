"""Logical-axis sharding: rules mapping logical axes -> mesh axes.

Models annotate parameters with logical axis names (via the param specs)
and activations with logical activation names (via ``shard_act``).  A
``ShardingPolicy`` maps those to physical mesh axes; the launcher
installs (mesh, policy) with ``use_sharding`` around tracing so the same
model code runs unsharded on 1 CPU device and fully sharded on 512.

Divisibility-aware: a rule only applies when the dimension size is
divisible by the mesh-axis size (falling through an ordered candidate
list otherwise) — this is what lets one policy cover head counts like 24
or 40 that don't divide a 16-way model axis (the attention falls back to
replicated weights + sequence-sharded compute, see DESIGN.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "ShardingPolicy",
    "use_sharding",
    "current_context",
    "shard_act",
    "spec_for_axes",
    "params_pspecs",
    "named_sharding_tree",
]

_tls = threading.local()


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Sharding rules.

    param_rules: logical param axis -> ordered candidates of mesh axes.
      Each candidate is a mesh-axis name or a tuple of names (joint
      sharding, e.g. FSDP x TP uses ("data", "model")).  First candidate
      whose size divides the dim (and whose axes are unused in the spec)
      wins; otherwise the dim is replicated.
    act_rules: logical activation name -> PartitionSpec template (tuple of
      mesh-axis names / tuples / None, may be shorter than the rank — the
      remaining dims are replicated).
    """

    param_rules: Mapping[str, Sequence[Any]]
    act_rules: Mapping[str, tuple]

    def candidates(self, axis_name: str) -> Sequence[Any]:
        return self.param_rules.get(axis_name, ())


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _axis_names(axis) -> tuple:
    if axis is None:
        return ()
    if isinstance(axis, (tuple, list)):
        return tuple(axis)
    return (axis,)


def spec_for_axes(
    axes: tuple, shape: tuple[int, ...], policy: ShardingPolicy, mesh: Mesh
) -> PartitionSpec:
    """PartitionSpec for one parameter from its logical axes + shape."""
    out, used = [], set()
    for dim, logical in zip(shape, axes):
        chosen = None
        if logical is not None:
            for cand in policy.candidates(logical):
                names = _axis_names(cand)
                if not names:
                    continue
                if any(n in used for n in names):
                    continue
                if dim % _axis_size(mesh, cand) != 0:
                    continue
                chosen = tuple(names) if len(names) > 1 else names[0]
                used.update(names)
                break
        out.append(chosen)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def params_pspecs(axes_tree, shapes_tree, policy: ShardingPolicy, mesh: Mesh):
    """Pytree of PartitionSpecs for a params pytree."""
    return jax.tree.map(
        lambda axes, arr: spec_for_axes(axes, arr.shape, policy, mesh),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def named_sharding_tree(pspec_tree, mesh: Mesh):
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


# ------------------------------------------------------------- context


@contextlib.contextmanager
def use_sharding(mesh: Mesh, policy: ShardingPolicy):
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = (mesh, policy)
    try:
        yield
    finally:
        _tls.ctx = prev


def current_context():
    return getattr(_tls, "ctx", None)


def shard_act(x, name: str):
    """Constrain an activation to the current policy's rule for ``name``.

    No-op outside a sharding context or when the rule doesn't apply
    (missing name, rank mismatch, or non-divisible dims — the fallback is
    always "let the partitioner decide").
    """
    ctx = current_context()
    if ctx is None:
        return x
    mesh, policy = ctx
    rule = policy.act_rules.get(name)
    if rule is None:
        return x
    # Template-level alternatives: a rule may be a LIST OF TUPLES tried in
    # order; the first template whose non-None dims all divide (and don't
    # conflict) wins.  E.g. attention activations: heads-sharded when the
    # head count divides the model axis, else sequence-sharded.
    if isinstance(rule, list) and rule and isinstance(rule[0], tuple):
        chosen_rule = None
        for tpl in rule:
            if len(tpl) > x.ndim:
                continue
            used_t: set = set()
            ok = True
            for i, axis in enumerate(tpl):
                if axis is None:
                    continue
                names = tuple(axis) if isinstance(axis, tuple) else (axis,)
                if any(n in used_t for n in names) or x.shape[i] % _axis_size(mesh, axis) != 0:
                    ok = False
                    break
                used_t.update(names)
            if ok:
                chosen_rule = tpl
                break
        if chosen_rule is None:
            return x
        rule = chosen_rule
    if len(rule) > x.ndim:
        return x
    spec = []
    used: set = set()
    for i, axis in enumerate(rule):
        # Each dim may carry an ordered candidate list: [cand1, cand2, ...].
        candidates = axis if isinstance(axis, list) else [axis]
        chosen = None
        for cand in candidates:
            if cand is None:
                continue
            names = tuple(cand) if isinstance(cand, tuple) else (cand,)
            if any(n in used for n in names):
                continue
            if x.shape[i] % _axis_size(mesh, cand) != 0:
                continue
            chosen = names if len(names) > 1 else names[0]
            used.update(names)
            break
        spec.append(chosen)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*spec)))
