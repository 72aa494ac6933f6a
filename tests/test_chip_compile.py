"""The serving path's device programs compile for a described TPU v5e.

No chip is attached: the TPU compiler installed with JAX compiles for a
topology that is only described, and refuses what the chip's compiler
would refuse (a Pallas kernel that does not lower through Mosaic, an f64
program it cannot emulate).  The shapes are the paper's: the three
applications' k-NN SneakPeek tiles, the Eq. 2 utility tile of a 12-request
window, and the compiled window programs of ``core/pipeline.py`` under x64
for one 12-request window; across the 2x2 mesh, the sharded scheduler's
exact argmax collectives.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import numpy as np
import pytest

from repro.core import fastpath, make_policy, pipeline
from repro.core.pipeline import WindowPipeline
from repro.data.applications import (
    APP_SPECS,
    build_benchmark_suite,
    make_requests,
    make_sneakpeek,
)
from repro.kernels.knn import ops as knn_ops
from repro.kernels.utility import ops as utility_ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


@pytest.mark.parametrize("q", [4, 128])
@pytest.mark.parametrize("app", sorted(APP_SPECS))
def test_knn_votes_compile_to_the_kernel(one_chip, app, q):
    """The public k-NN entry point lowers its Pallas kernel through Mosaic
    (not interpret mode) at each application's train-set shape."""
    sp = make_sneakpeek(APP_SPECS[app])
    n, d = sp.train_x.shape
    compiled = knn_ops.knn_class_votes.lower(
        _on(one_chip, (q, d), np.float32), _on(one_chip, (n, d), np.float32),
        _on(one_chip, (n,), np.int32), sp.k, sp.num_classes,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_utility_kernel_compiles(one_chip):
    compiled = utility_ops.utility_scores.lower(
        _on(one_chip, (12, 3), np.float32), _on(one_chip, (12,), np.float32),
        _on(one_chip, (12, 3), np.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _captured_program(monkeypatch, module, builder: str, policy):
    """Run one 12-request window through the pipeline on the CPU and
    capture the compiled program ``module.builder`` returns and the
    arguments of its last call."""
    seen = {}
    real = getattr(module, builder)

    def build(*a, **kw):
        prog = real(*a, **kw)

        def call(*args):
            seen["prog"], seen["args"] = prog, args
            return prog(*args)

        return call

    monkeypatch.setattr(module, builder, build)
    apps, sneaks = build_benchmark_suite(backend="numpy", seed=0)
    reqs = make_requests(list(APP_SPECS.values()), per_app=4, seed=0)
    WindowPipeline(apps, sneakpeeks=sneaks, policy=policy).run(reqs, 0.1)
    return seen["prog"], seen["args"]


@pytest.mark.parametrize("module,builder,policy", [
    (pipeline, "_per_request_program", make_policy("LO-EDF", pipeline=True)),
    # tau=0 keeps the window off the host brute-force branch.
    (pipeline, "_grouped_program", make_policy("SneakPeek", pipeline=True, tau=0)),
    # The grouped path's Eq. 9/12 program (f64 matmul, exp, var).
    (fastpath, "_stacked_program_jax", make_policy("SneakPeek", pipeline=True, tau=0)),
], ids=["per_request", "grouped", "eq9_eq12"])
def test_window_program_compiles_under_x64(one_chip, monkeypatch, module, builder, policy):
    prog, args = _captured_program(monkeypatch, module, builder, policy)
    with jax.enable_x64(True):
        specs = jax.tree.map(
            lambda x: _on(one_chip, np.shape(x), np.asarray(x).dtype), args)
        hlo = prog.lower(*specs).compile().as_text()
    assert "f64" in hlo  # the decisions stay in float64 on the chip


def test_shard_argmax_collectives_compile_on_a_2x2_mesh(topo):
    """The sharded scheduler's exact global argmax (float64 utilities,
    int64 tie-break ranks) compiles across four chips: a TPU all-reduces
    64-bit values by sum only, so max/min must not lower to one."""
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.shard import _owner_bcast, _pick_allreduce

    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))

    def pick(u, rank):
        r_star = _pick_allreduce(jnp, jax, u, rank)
        return r_star, _owner_bcast(jnp, jax, jnp.any(rank == r_star), u.max())

    prog = jax.jit(jax.shard_map(pick, mesh=mesh, in_specs=(P("shard"), P("shard")),
                                 out_specs=(P(), P()), check_vma=False))
    row = NamedSharding(mesh, P("shard"))
    with jax.enable_x64(True):
        hlo = prog.lower(jax.ShapeDtypeStruct((16,), np.float64, sharding=row),
                         jax.ShapeDtypeStruct((16,), np.int64, sharding=row)
                         ).compile().as_text()
    assert "all-gather" in hlo or "all-reduce" in hlo
