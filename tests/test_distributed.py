"""Distribution layer: sharding rules, policies, and a subprocess mini
dry-run on 16 forced host devices (tests must not set XLA_FLAGS in-process)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec

from repro.configs import ARCHS
from repro.distributed.policies import default_mode, make_policy
from repro.distributed.sharding import ShardingPolicy, spec_for_axes

REPO = Path(__file__).resolve().parents[1]


class _FakeMesh:
    """Just enough Mesh for spec_for_axes (shape lookups)."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_spec_for_axes_divisibility_fallback():
    mesh = _FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy(
        param_rules={"heads": ["model"], "embed": [("data", "model"), "data"]},
        act_rules={},
    )
    # heads=24 does not divide 16 -> replicated; embed=1536 divides 256
    # (trailing Nones are stripped — PartitionSpec semantics)
    ps = spec_for_axes(("embed", "heads", None), (1536, 24, 64), pol, mesh)
    assert ps == PartitionSpec(("data", "model"))
    # heads=32 divides, but embed's joint candidate already consumed
    # "model" -> heads stays replicated (no axis reuse within one spec)
    ps = spec_for_axes(("embed", "heads", None), (1536, 32, 64), pol, mesh)
    assert ps == PartitionSpec(("data", "model"))
    # with embed restricted to "data", heads takes model
    pol2 = ShardingPolicy(param_rules={"heads": ["model"], "embed": ["data"]}, act_rules={})
    ps = spec_for_axes(("embed", "heads", None), (1536, 32, 64), pol2, mesh)
    assert ps == PartitionSpec("data", "model")


def test_spec_no_axis_reuse():
    mesh = _FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy(
        param_rules={"vocab": ["model"], "embed": [("data", "model"), "data"]},
        act_rules={},
    )
    # vocab takes model; embed's joint candidate conflicts -> falls to data
    ps = spec_for_axes(("vocab", "embed"), (32000, 2048), pol, mesh)
    assert ps == PartitionSpec("model", "data")


def test_default_modes():
    assert default_mode(ARCHS["tinyllama-1.1b"], "train") == "fsdp"
    assert default_mode(ARCHS["llama4-scout-17b-16e"], "train") == "ep_fsdp"
    assert default_mode(ARCHS["gemma-7b"], "decode") == "tp"
    assert default_mode(ARCHS["llama4-maverick-400b-128e"], "prefill") == "ep_tp"


def test_policies_build_for_all_archs_and_steps():
    mesh = _FakeMesh({"data": 16, "model": 16})
    for arch, cfg in ARCHS.items():
        for step in ("train", "prefill", "decode"):
            pol = make_policy(cfg, step, mesh)
            assert "act_btd" in pol.act_rules


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Real 16-device SPMD compile of a reduced arch through the full
    policy/shardings/steps stack (the 512-device version is the deliverable
    run in launch/dryrun.py; this guards the machinery in CI)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import json, dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.configs.shapes import ShapeSpec
        from repro.distributed.policies import make_policy
        from repro.distributed.sharding import use_sharding
        from repro.launch import shardings as shd
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_step, make_decode_step
        from repro.models import LM
        from repro.training.optimizer import OptimizerConfig, init_opt_state

        cfg = dataclasses.replace(
            ARCHS["tinyllama-1.1b"].reduced(), d_model=64, vocab_size=256,
            num_heads=4, num_kv_heads=4, head_dim=16, d_ff=256, dtype="bfloat16")
        mesh = make_mesh((4, 4), ("data", "model"))
        model = LM(cfg)
        out = {}
        # train
        pol = make_policy(cfg, "train", mesh)
        with mesh, use_sharding(mesh, pol):
            p_sh = shd.as_named(shd.param_pspecs(model, pol, mesh), mesh)
            opt_cfg = OptimizerConfig()
            o_specs = shd.opt_state_pspecs(model, pol, mesh, opt_cfg)
            o_sh = shd.as_named(o_specs, mesh)
            abstract_opt = jax.eval_shape(lambda p: init_opt_state(p, opt_cfg), model.abstract_params())
            tok = jax.ShapeDtypeStruct((16, 33), jnp.int32)
            tok_sh = jax.NamedSharding(mesh, shd.token_pspec(16, mesh, full_mesh=True))
            c = jax.jit(make_train_step(model, opt_cfg),
                        in_shardings=(p_sh, o_sh, {"tokens": tok_sh}),
                        out_shardings=(p_sh, o_sh, None),
                        ).lower(model.abstract_params(), abstract_opt, {"tokens": tok}).compile()
            ca = c.cost_analysis() or {}
            out["train_flops"] = float(ca.get("flops", 0))
        # decode
        pol = make_policy(cfg, "decode", mesh)
        with mesh, use_sharding(mesh, pol):
            p_sh = shd.as_named(shd.param_pspecs(model, pol, mesh), mesh)
            kv = model.abstract_cache(8, 64)
            kv_sh = shd.as_named(shd.cache_pspecs(kv, mesh), mesh)
            tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
            tok_sh = jax.NamedSharding(mesh, shd.token_pspec(8, mesh))
            c = jax.jit(make_decode_step(model),
                        in_shardings=(p_sh, kv_sh, tok_sh),
                        out_shardings=(None, kv_sh),
                        donate_argnums=(1,),
                        ).lower(model.abstract_params(), kv, tok).compile()
            out["decode_ok"] = True
        print(json.dumps(out))
        """
    )
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=420
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["decode_ok"] and out["train_flops"] > 0


def test_production_mesh_shapes():
    """make_production_mesh contract (without initializing 512 devices:
    validated shape math only; the real construction is exercised by
    launch/dryrun.py and, scaled down, by the real-mesh tests below)."""
    import inspect
    from repro.launch import mesh as mesh_mod

    src = inspect.getsource(mesh_mod.make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src


@pytest.mark.skipif(
    jax.local_device_count() < 256,
    reason="make_production_mesh needs a real 256-device (16x16) slice; "
    "the shape contract is covered by test_production_mesh_shapes and a "
    "scaled-down real construction by test_real_mesh_spec_round_trip",
)
def test_production_mesh_real_construction():
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh()
    assert dict(mesh.shape) == {"data": 16, "model": 16}


@pytest.mark.skipif(
    jax.local_device_count() < 4,
    reason="needs >= 4 forced host devices "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=4 before jax "
    "import; the CI shard-tests leg sets it)",
)
def test_real_mesh_spec_round_trip():
    """Same mesh geometry as production (data x model), scaled to 2x2 on
    real (forced-host) devices: specs resolved by spec_for_axes place
    arrays with the expected per-device blocks."""
    import numpy as np

    from repro.distributed.sharding import named_sharding_tree
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 2), ("data", "model"))
    pol = ShardingPolicy(
        param_rules={"embed": ["data"], "heads": ["model"]}, act_rules={}
    )
    spec = spec_for_axes(("embed", "heads"), (8, 6), pol, mesh)
    assert spec == PartitionSpec("data", "model")
    ns = named_sharding_tree({"w": spec}, mesh)
    arr = jax.device_put(np.arange(48.0).reshape(8, 6), ns["w"])
    shards = arr.addressable_shards
    assert len(shards) == 4
    assert all(s.data.shape == (4, 3) for s in shards)
    assert np.array_equal(np.asarray(arr), np.arange(48.0).reshape(8, 6))
