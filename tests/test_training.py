"""Training substrate: optimizer, checkpointing, fault tolerance, compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; example tests still run
    from _hypothesis_stub import given, settings, st

from repro.configs import ARCHS
from repro.data import LMDataConfig, LMDataset
from repro.models import LM
from repro.training import (
    OptimizerConfig,
    Trainer,
    TrainerConfig,
    adamw_step,
    checkpoint as ckpt,
    compressed_psum_tree,
    dequantize8,
    init_error_feedback,
    init_opt_state,
    quantize8,
)
from repro.training.optimizer import learning_rate


# ---------------------------------------------------------------- optimizer


def test_adamw_matches_reference_numpy():
    """Our AdamW against a hand-rolled numpy implementation."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(4, 3)).astype(np.float32)
    params = {"w": jnp.asarray(w)}
    cfg = OptimizerConfig(learning_rate=1e-2, warmup_steps=0, total_steps=10**9,
                          weight_decay=0.1, grad_clip=0.0, min_lr_ratio=1.0)
    state = init_opt_state(params, cfg)
    m = np.zeros_like(w)
    v = np.zeros_like(w)
    wn = w.copy()
    for step in range(1, 6):
        g = rng.normal(size=w.shape).astype(np.float32)
        params, state, _ = adamw_step({"w": jnp.asarray(g)}, state, params, cfg)
        m = 0.9 * m + 0.1 * g
        v = 0.95 * v + 0.05 * g * g
        mh = m / (1 - 0.9**step)
        vh = v / (1 - 0.95**step)
        wn = wn - 1e-2 * (mh / (np.sqrt(vh) + 1e-8) + 0.1 * wn)
        np.testing.assert_allclose(np.asarray(params["w"]), wn, atol=1e-5)


def test_quantized_moments_track_fp32():
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
    cfg_f = OptimizerConfig(learning_rate=1e-2, warmup_steps=0, grad_clip=0.0)
    cfg_q = OptimizerConfig(learning_rate=1e-2, warmup_steps=0, grad_clip=0.0, quantize_moments=True)
    s_f = init_opt_state(params, cfg_f)
    s_q = init_opt_state(params, cfg_q)
    p_f = p_q = params
    for step in range(10):
        g = {"w": jnp.asarray(rng.normal(size=(64, 64)).astype(np.float32))}
        p_f, s_f, _ = adamw_step(g, s_f, p_f, cfg_f)
        p_q, s_q, _ = adamw_step(g, s_q, p_q, cfg_q)
    diff = float(jnp.abs(p_f["w"] - p_q["w"]).max())
    scale = float(jnp.abs(p_f["w"] - params["w"]).max())
    assert diff < 0.25 * scale, f"int8 moments diverged: {diff} vs update scale {scale}"
    assert s_q["m"]["w"]["q"].dtype == jnp.int8


def test_lr_schedule():
    cfg = OptimizerConfig(learning_rate=1.0, warmup_steps=10, total_steps=110, min_lr_ratio=0.1)
    assert float(learning_rate(cfg, 0)) == 0.0
    assert float(learning_rate(cfg, 10)) == pytest.approx(1.0)
    assert float(learning_rate(cfg, 110)) == pytest.approx(0.1)


# ---------------------------------------------------------------- checkpoint


def _tree():
    return {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
        "lst": [jnp.zeros((2,)), jnp.asarray(3)],
    }


def test_checkpoint_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        state = _tree()
        ckpt.save(d, 7, state, metadata={"note": "x"})
        restored, meta = ckpt.restore(d)
        assert meta == {"note": "x"}
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert isinstance(restored["lst"], list)


def test_checkpoint_atomicity_ignores_tmp():
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, _tree())
        # simulate a crashed partial write
        os.makedirs(os.path.join(d, "step_00000002.tmp"))
        assert ckpt.latest_step(d) == 1


def test_checkpoint_retention():
    with tempfile.TemporaryDirectory() as d:
        for s in range(6):
            ckpt.save(d, s, _tree(), keep=2)
        assert ckpt.list_steps(d) == [4, 5]


def test_checkpoint_corruption_detected():
    with tempfile.TemporaryDirectory() as d:
        path = ckpt.save(d, 3, _tree())
        npz = path / "arrays.npz"
        data = dict(np.load(npz))
        key = sorted(data.keys())[0]
        data[key] = data[key] + 1
        np.savez(npz, **data)
        with pytest.raises(IOError):
            ckpt.restore(d, 3)


def test_checkpoint_elastic_reshard():
    """Save unsharded, restore with explicit shardings (reshard-on-load)."""
    from jax.sharding import NamedSharding, PartitionSpec, Mesh

    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    sh = {"w": NamedSharding(mesh, PartitionSpec())}
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 0, state)
        restored, _ = ckpt.restore(d, shardings=sh)
        assert restored["w"].sharding == sh["w"]


# ---------------------------------------------------------------- trainer


def _mk_trainer(d, total=30, every=10, fault_hook=None, max_restarts=3):
    cfg = ARCHS["mamba2-130m"].reduced()
    model = LM(cfg)
    ds = LMDataset(LMDataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=4, kind="markov"))
    return Trainer(
        model, ds,
        # NB: fixed schedule horizon — the LR schedule must not depend on how
        # many steps THIS incarnation runs, or resume changes the trajectory.
        opt_cfg=OptimizerConfig(learning_rate=3e-3, warmup_steps=2, total_steps=1000),
        cfg=TrainerConfig(total_steps=total, checkpoint_every=every, checkpoint_dir=d,
                          log_every=5, max_restarts=max_restarts),
        fault_hook=fault_hook,
    )


def test_trainer_runs_and_learns():
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, total=30)
        step, params, opt, summary = tr.train()
        assert step == 29 and summary["restarts"] == 0
        assert summary["losses"][-1] < summary["losses"][0]


def test_trainer_recovers_from_injected_faults():
    """Faults at steps 7 and 15 -> restore from checkpoints, same final step."""
    faults = {7, 15}

    def hook(step):
        if step in faults:
            faults.remove(step)
            raise RuntimeError(f"injected node failure at step {step}")

    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, total=25, every=5, fault_hook=hook)
        step, params, opt, summary = tr.train()
        assert step == 24
        assert summary["restarts"] == 2
        assert not faults  # both triggered


def test_trainer_resume_from_checkpoint_is_deterministic():
    """Train 20 straight vs train 10 + resume 10 -> identical params
    (stateless data pipeline + checkpointed optimizer state)."""
    with tempfile.TemporaryDirectory() as d1, tempfile.TemporaryDirectory() as d2:
        tr_a = _mk_trainer(d1, total=20, every=100)
        _, params_a, _, _ = tr_a.train()

        tr_b1 = _mk_trainer(d2, total=10, every=100)
        tr_b1.train()  # saves final at step 9
        tr_b2 = _mk_trainer(d2, total=20, every=100)
        _, params_b, _, _ = tr_b2.train(resume=True)
        for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_trainer_exhausts_restarts():
    def hook(step):
        raise RuntimeError("always failing")

    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, total=10, max_restarts=2, fault_hook=hook)
        with pytest.raises(RuntimeError, match="max_restarts"):
            tr.train()


# ---------------------------------------------------------------- compression


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_quantize8_bounded_error(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32) * rng.uniform(0.1, 10))
    q, scale = quantize8(x)
    err = jnp.abs(dequantize8(q, scale) - x)
    assert float((err <= scale / 2 + 1e-9).all())  # half-ULP rounding bound


def test_error_feedback_preserves_signal():
    """Sum of compressed grads + residual == sum of true grads (no bias)."""
    rng = np.random.default_rng(3)
    grads = [{"w": jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))} for _ in range(20)]
    ef = init_error_feedback(grads[0])
    total_out = jnp.zeros((16, 32))
    total_in = jnp.zeros((16, 32))
    for g in grads:
        out, ef = compressed_psum_tree(g, ef)
        total_out = total_out + out["w"]
        total_in = total_in + g["w"]
    # residual is the only difference; it stays O(one quantization step)
    resid = float(jnp.abs(total_in - total_out - ef["w"]).max())
    assert resid < 1e-4
    drift = float(jnp.abs(ef["w"]).max())
    one_step_scale = float(jnp.abs(grads[0]["w"]).max()) / 127
    assert drift < 20 * one_step_scale  # bounded accumulation, not linear in steps


def test_compressed_psum_under_shard_map():
    """Cross-'pod' int8 all-reduce with a 1-device mesh (n=1 degenerate) —
    validates the shard_map plumbing; multi-device covered by the
    subprocess dry-run test."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("pod",))
    g = {"w": jnp.ones((2, 8), jnp.float32)}
    ef = init_error_feedback(g)

    def f(g, e):
        return compressed_psum_tree(g, e, axis_name="pod")

    out, new_ef = jax.shard_map(
        f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False
    )(g, ef)
    np.testing.assert_allclose(np.asarray(out["w"]), np.ones((2, 8)), atol=1e-2)


def test_trainer_preemption_checkpoint():
    """SIGTERM-style preemption: flag set mid-run -> checkpoint + clean stop."""
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, total=50, every=1000)  # no periodic checkpoints

        orig_hook = {"count": 0}

        def hook(step):
            orig_hook["count"] += 1
            if step == 7:
                tr._preempted = True  # what the SIGTERM handler sets

        tr.fault_hook = hook
        step, params, opt, summary = tr.train()
        assert summary["preempted"]
        assert step < 49
        # a checkpoint was committed on the way out; a fresh trainer resumes
        assert ckpt.latest_step(d) is not None
        tr2 = _mk_trainer(d, total=12, every=1000)
        step2, *_ = tr2.train(resume=True)
        assert step2 == 11
