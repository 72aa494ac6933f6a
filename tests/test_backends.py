"""Executor-backend protocol tests: the three substrates behind one
interface, KV-cache byte accounting, cost-model latency derivation, and
the regression guarantees the refactor promised (default path unchanged,
plain pool dispatch identical to the degenerate supervised gather)."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.accuracy import ModelProfile
from repro.core.multiworker import Worker
from repro.core.scheduler import make_policy
from repro.core.types import Application, Request, Schedule, ScheduleEntry
from repro.models.kvcache import cache_bytes
from repro.serving import (
    CompiledBackend,
    CostModelBackend,
    EdgeServer,
    ExecutionReport,
    ExecutorBackend,
    ExecutorPool,
    LMExecutor,
    ProfiledBackend,
    costmodel_latency_model,
    costmodel_profile,
    lm_latency_model,
)
from repro.serving.backends import _affine_fit


# --------------------------------------------------------------- helpers


def _reduced(arch):
    return get_config(arch).reduced()


def _entries(variant_for, n, arrival=0.0, deadline=60.0, batch_of=None):
    entries = []
    for i in range(n):
        r = Request(rid=i, app="app", arrival_s=arrival, deadline_s=deadline,
                    features=np.zeros(4), true_label=0)
        entries.append(ScheduleEntry(
            request=r, model=variant_for(i), order=i, worker=0,
            batch_id=batch_of(i) if batch_of else -1))
    return entries


def _prompt_fn(r):
    return np.arange(3 + (r.rid % 3), dtype=np.int32)


class SyntheticBackend(ExecutorBackend):
    """Deterministic no-compute backend: reports depend only on the
    batch, never on wall clock — lets dispatch-path tests compare
    reports exactly."""

    provenance = "realized"

    def run_batch(self, model_name, prompts, request_ids, class_token_ids=None):
        b = prompts.shape[0]
        return ExecutionReport(
            request_ids=list(request_ids), model=model_name, batch_size=b,
            swap_s=0.0, prefill_s=0.01, decode_s=0.001 * b,
            tokens=np.zeros((b, self.new_tokens), np.int32),
            predictions=[None] * b)

    def latency_model(self, model_name, batch=1):
        return 0.01 + 0.001 * batch

    def model_bytes(self, model_name, batch=None, max_len=None):
        return 1_000

    def swap_cost(self, model_name):
        return 0.001


# ------------------------------------------------- kvcache.cache_bytes


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma-7b"])
def test_cache_bytes_linear_in_batch_and_max_len(arch):
    cfg = get_config(arch)
    # Linear in batch: equal increments at fixed max_len.
    c1, c2, c3 = (cache_bytes(cfg, b, 128) for b in (1, 2, 3))
    assert c2 - c1 == c3 - c2 > 0
    # Linear in max_len: equal increments at fixed batch (these archs
    # carry attention KV, which grows with sequence length).
    l1, l2, l3 = (cache_bytes(cfg, 2, m) for m in (64, 128, 192))
    assert l2 - l1 == l3 - l2 > 0


def test_cache_bytes_ssd_state_is_length_independent():
    # Pure-SSD variants keep a fixed-size recurrent state: batch-linear,
    # but max_len must NOT change the footprint.
    cfg = get_config("mamba2-130m")
    c1, c2, c3 = (cache_bytes(cfg, b, 128) for b in (1, 2, 3))
    assert c2 - c1 == c3 - c2 > 0
    assert cache_bytes(cfg, 2, 64) == cache_bytes(cfg, 2, 256)


# --------------------------------------------- cost-model latency path


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma-7b"])
def test_costmodel_latency_monotone_and_agrees_with_fallback(arch):
    fixed, per_item = costmodel_latency_model(arch)
    assert fixed > 0 and per_item > 0
    lat = [fixed + per_item * b for b in (1, 2, 4, 8)]
    assert all(b < a for b, a in zip(lat, lat[1:]))
    # Same device count, same HW constants: the census and the analytic
    # fallback agree within 2x at serving batch sizes.
    f_fb, p_fb = lm_latency_model("/nonexistent", arch)
    for b in (1, 2, 4):
        ratio = (fixed + per_item * b) / (f_fb + p_fb * b)
        assert 0.5 < ratio < 2.0, (arch, b, ratio)


def test_costmodel_profile_provenance_and_fields():
    p = costmodel_profile("tinyllama-1.1b", [0.9, 0.8, 0.7])
    assert p.provenance == "costmodel"
    assert p.latency_model is not None and p.latency_s > 0
    assert p.memory_bytes == 2 * get_config("tinyllama-1.1b").param_count()
    assert p.load_latency_s > 0


def test_costmodel_accepts_composed_cost_totals():
    totals = {"flops": 1e12, "bytes": 1e10, "collective_bytes": 1e8, "batch": 8}
    f, p = costmodel_latency_model("tinyllama-1.1b", costs=totals)
    assert f > 0 and p > 0


def test_model_profile_provenance_validation():
    with pytest.raises(ValueError):
        ModelProfile(name="m", recalls=[0.5], latency_s=0.1, provenance="guessed")


# ----------------------------------------------------- ProfiledBackend


def test_default_executor_accounting_matches_legacy_formula():
    # The refactor promise: with no backend= passed, LMExecutor's swap
    # sizes and load latencies are byte-for-byte the pre-backend
    # constants (weight bytes at dtype, staged at 25 GB/s).
    variants = {"small": (_reduced("mamba2-130m"), 0),
                "big": (_reduced("tinyllama-1.1b"), 1)}
    ex = LMExecutor(variants, new_tokens=2)
    assert isinstance(ex.backend, ProfiledBackend)
    assert ex.backend.provenance == "profiled"
    for name, (cfg, _) in variants.items():
        bytes_ = (2 if cfg.dtype == "bfloat16" else 4) * cfg.param_count()
        assert ex.swaps.sizes[name] == bytes_
        assert ex.swaps.load_latency[name] == bytes_ / 25e9


def test_profiled_backend_spawn_is_independent():
    be = ProfiledBackend({"m": (_reduced("mamba2-130m"), 0)}, new_tokens=2)
    clone = be.spawn()
    assert clone is not be and clone.variants == be.variants
    assert clone.new_tokens == be.new_tokens


# ----------------------------------------------------- CompiledBackend


def test_compiled_backend_runs_real_forward_and_fits_latency():
    be = CompiledBackend({"m": (_reduced("mamba2-130m"), 0)}, new_tokens=2)
    r = be.run_batch("m", np.ones((3, 5), np.int32), [0, 1, 2],
                     class_token_ids=np.array([1, 2]))
    assert r.tokens.shape == (3, 2)
    assert len(r.predictions) == 3 and all(p in (0, 1) for p in r.predictions)
    # The fit is fed known (batch, seconds) observations, not CPU timings.
    for b, t in ((1, 0.004), (2, 0.006), (4, 0.010), (4, 0.010)):
        be._record("m", b, t)
    fixed, per_item = be.affine("m")
    assert fixed == pytest.approx(0.002) and per_item == pytest.approx(0.002)
    assert be.latency_model("m", 4) >= be.latency_model("m", 1)
    p = be.profile("m", [0.9, 0.8])
    assert p.provenance == "realized" and p.latency_s == pytest.approx(0.004)


@pytest.mark.parametrize("obs", [
    ((1, 0.005), (2, 0.020), (4, 0.040)),  # intercept < 0: noise-steep slope
    ((1, 0.010), (2, 0.010)),  # flat: intercept > 0, slope 0
    ((1, 0.012), (2, 0.008), (4, 0.009)),  # negative slope
])
def test_affine_fit_keeps_a_positive_fixed_term(obs):
    """Whatever the noise does to the least-squares line, the fitted model
    keeps fixed > 0, per_item >= 0, and stays within the observed range."""
    fixed, per_item = _affine_fit(obs)
    assert fixed > 0 and per_item >= 0
    ts = [t for _, t in obs]
    for b, _ in obs:
        assert min(ts) - 1e-12 <= fixed + per_item * b <= max(ts) + 1e-12


def test_compiled_backend_continuous_batching_splits_reports():
    be = CompiledBackend({"m": (_reduced("mamba2-130m"), 0)}, new_tokens=2)
    reports = be.run_batches(
        "m", [np.ones((2, 4), np.int32), np.ones((3, 6), np.int32)],
        [[10, 11], [20, 21, 22]])
    assert [r.request_ids for r in reports] == [[10, 11], [20, 21, 22]]
    assert [r.batch_size for r in reports] == [2, 3]
    assert reports[0].tokens.shape == (2, 2) and reports[1].tokens.shape == (3, 2)
    # The fused pass's measured seconds split proportionally to rows.
    total = sum(r.prefill_s + r.decode_s for r in reports)
    assert reports[1].prefill_s == pytest.approx(reports[0].prefill_s * 1.5)
    assert total > 0


def _prompts(rows, seq=8, vocab=128, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (rows, seq)).astype(np.int32)


@pytest.mark.parametrize("new_tokens", [2, 4])
@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-130m", "granite-4.0-h-small"])
def test_compiled_backend_tokens_equal_greedy_generate(arch, new_tokens):
    """Tokens picked inside the jitted programs are LM.generate's greedy
    tokens; 3 rows run padded to 4."""
    import jax.numpy as jnp

    from repro.models import LM

    cfg = _reduced(arch)
    be = CompiledBackend({"m": (cfg, 0)}, new_tokens=new_tokens, seq_multiple=8)
    prompts = _prompts(3, vocab=cfg.vocab_size)
    r = be.run_batch("m", prompts, [0, 1, 2])
    lm = LM(cfg)
    want = lm.generate(lm.init(0), jnp.asarray(prompts), new_tokens, temperature=0.0)
    assert r.tokens.dtype == np.int32
    np.testing.assert_array_equal(r.tokens, np.asarray(want)[:, -new_tokens:])


def test_compiled_backend_class_preds_equal_host_argmax_with_a_tie():
    """``preds`` is the first-index argmax over the gathered option logits
    of the prefill, as the host's gather and argmax would give, a
    duplicated option id (an exact tie) included."""
    import jax
    import jax.numpy as jnp

    cfg = _reduced("tinyllama-1.1b")
    be = CompiledBackend({"m": (cfg, 0)}, new_tokens=2, seq_multiple=8)
    prompts = _prompts(4, vocab=cfg.vocab_size, seed=3)
    model, params = be._get("m")
    logits, _ = jax.jit(model.prefill, static_argnames="max_len")(
        params, jnp.asarray(prompts), max_len=10)
    logits = np.asarray(logits)
    top = int(np.argmax(logits[0]))
    low = int(np.argmin(logits[0]))
    ids = np.array([low, top, top, (top + 1) % cfg.vocab_size])  # row 0: tie at 1 and 2
    r = be.run_batch("m", prompts, [0, 1, 2, 3], class_token_ids=ids)
    want = np.argmax(logits[:, ids], axis=-1)
    assert want[0] == 1
    assert [int(p) for p in r.predictions] == [int(w) for w in want]
    np.testing.assert_array_equal(r.tokens[:, 0], np.argmax(logits, axis=-1))


def test_profiled_and_compiled_backends_pick_the_same_tokens():
    cfg = _reduced("mamba2-130m")
    prompts = _prompts(4, vocab=cfg.vocab_size, seed=5)
    ids = np.array([3, 7, 11])
    a = ProfiledBackend({"m": (cfg, 0)}, new_tokens=3).run_batch(
        "m", prompts, [0, 1, 2, 3], class_token_ids=ids)
    b = CompiledBackend({"m": (cfg, 0)}, new_tokens=3, seq_multiple=8).run_batch(
        "m", prompts, [0, 1, 2, 3], class_token_ids=ids)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert [int(p) for p in a.predictions] == [int(p) for p in b.predictions]


def test_compiled_backend_reuses_the_donated_cache_across_forwards():
    cfg = _reduced("tinyllama-1.1b")
    be = CompiledBackend({"m": (cfg, 0)}, new_tokens=4, seq_multiple=8)
    prompts = _prompts(3, vocab=cfg.vocab_size, seed=7)
    runs = [be.run_batch("m", prompts, [0, 1, 2]).tokens for _ in range(3)]
    assert be.cold_forwards == 1
    for toks in runs[1:]:
        np.testing.assert_array_equal(toks, runs[0])


@pytest.mark.parametrize("backend", [CompiledBackend, ProfiledBackend])
def test_host_syncs_two_per_forward(backend):
    be = backend({"m": (_reduced("mamba2-130m"), 0)}, new_tokens=3)
    assert be.host_syncs == 0
    prompts = np.ones((2, 8), np.int32)
    for n in range(1, 4):
        be.run_batch("m", prompts, [0, 1], class_token_ids=np.array([1, 2]) if n == 2 else None)
        assert be.host_syncs == 2 * n
    if backend is CompiledBackend:
        be.run_batches("m", [prompts, prompts[:1]], [[0, 1], [2]])
        assert be.host_syncs == 8


def test_compiled_backend_model_bytes_includes_kv_cache():
    cfg = _reduced("tinyllama-1.1b")
    be = CompiledBackend({"m": (cfg, 0)}, new_tokens=2)
    weights = (2 if cfg.dtype == "bfloat16" else 4) * cfg.param_count()
    assert be.model_bytes("m", batch=1, max_len=64) > weights
    assert be.model_bytes("m", batch=4, max_len=64) > be.model_bytes("m", batch=1, max_len=64)


def test_executor_merges_consecutive_same_model_batches():
    # Through LMExecutor.execute_schedule, a window's consecutive
    # same-model batches fuse into one forward (swap charged once) while
    # short-circuit entries stay zero-cost.
    be = CompiledBackend({"m": (_reduced("mamba2-130m"), 0)}, new_tokens=2)
    ex = LMExecutor(backend=be)
    entries = _entries(lambda i: "m", 4, batch_of=lambda i: i // 2)
    reports = ex.execute_schedule(Schedule(entries=entries), _prompt_fn)
    assert len(reports) == 2
    assert reports[0].swap_s > 0 and reports[1].swap_s == 0.0
    assert ex.swaps.swap_count == 1


# ---------------------------------------------------- CostModelBackend


def test_costmodel_backend_synthetic_reports_and_profiles():
    be = CostModelBackend({"big": "gemma-7b", "small": "tinyllama-1.1b"},
                          prompt_tokens=128, new_tokens=16)
    r = be.run_batch("big", np.zeros((4, 8), np.int32), [0, 1, 2, 3])
    assert r.tokens.shape == (4, 0) and r.predictions == [None] * 4
    assert r.prefill_s > 0 and r.decode_s > 0
    assert r.total_s == pytest.approx(be.latency_model("big", 4))
    profs = be.profiles({"big": [0.95, 0.9], "small": [0.8, 0.7]})
    assert set(profs) == {"big", "small"}
    assert all(p.provenance == "costmodel" for p in profs.values())
    # Bigger model, bigger everything.
    assert profs["big"].latency_s > profs["small"].latency_s
    assert profs["big"].memory_bytes > profs["small"].memory_bytes


def test_costmodel_backend_drives_executor_without_devices():
    be = CostModelBackend({"m": "mamba2-130m"}, prompt_tokens=32, new_tokens=4)
    ex = LMExecutor(backend=be)
    entries = _entries(lambda i: "m", 3)
    reports = ex.execute_schedule(Schedule(entries=entries), _prompt_fn)
    assert len(reports) == 3
    assert reports[0].swap_s > 0  # cold load charged by the SwapManager
    assert all(r.total_s > 0 for r in reports)


# ------------------------------------- pool dispatch collapse (plain ==
# ------------------------------------- degenerate supervised gather)


def _pool_schedule():
    entries = []
    for i in range(6):
        r = Request(rid=i, app="app", arrival_s=0.0, deadline_s=60.0,
                    features=np.zeros(4), true_label=0)
        entries.append(ScheduleEntry(
            request=r, model="m", order=i, worker=i % 2, batch_id=i // 2))
    return Schedule(entries=entries)


def _report_key(r):
    return (r.worker, r.request_ids, r.model, r.batch_size,
            r.swap_s, r.prefill_s, r.decode_s)


def test_plain_pool_path_unchanged_by_supervised_collapse():
    # execute_schedule is now the supervised gather with faults=None,
    # timeout_s=None; with a deterministic backend the reports must be
    # EXACTLY what the supervised path yields — and in the same
    # (ascending worker, dispatch) order the plain path always promised.
    workers = [Worker(wid=0, speed=1.0), Worker(wid=1, speed=1.0)]

    def make_pool():
        return ExecutorPool(
            workers, backend_factory=lambda: SyntheticBackend({"m": (None, 0)}))

    plain = make_pool().execute_schedule(_pool_schedule(), _prompt_fn)
    outcome = make_pool().execute_supervised(_pool_schedule(), _prompt_fn)
    assert outcome.failures == [] and outcome.timed_out == []
    assert [_report_key(r) for r in plain] == [_report_key(r) for r in outcome.reports]
    assert [r.worker for r in plain] == sorted(r.worker for r in plain)


def test_plain_pool_path_still_raises_after_joining_all_lanes():
    class ExplodingBackend(SyntheticBackend):
        def run_batch(self, model_name, prompts, request_ids, class_token_ids=None):
            if 0 in request_ids:
                raise RuntimeError("boom")
            return super().run_batch(model_name, prompts, request_ids, class_token_ids)

    workers = [Worker(wid=0, speed=1.0), Worker(wid=1, speed=1.0)]
    pool = ExecutorPool(
        workers, backend_factory=lambda: ExplodingBackend({"m": (None, 0)}))
    with pytest.raises(RuntimeError, match="boom"):
        pool.execute_schedule(_pool_schedule(), _prompt_fn)
    assert pool.wall_s > 0  # the gather accounted wall time before raising


# --------------------------------------------- EdgeServer integration


def _one_model_app(profile):
    return {"app": Application(name="app", models=[profile],
                               penalty="step", prior=np.full(2, 0.5))}


def _requests(n):
    return [
        Request(rid=i, app="app", arrival_s=0.01 * (i + 1), deadline_s=10.0,
                features=np.zeros(4), true_label=i % 2, theta=np.full(2, 0.5))
        for i in range(n)
    ]


def test_edge_server_default_provenance_is_profiled():
    prof = ModelProfile(name="m", recalls=[0.9, 0.8], latency_s=0.01)
    srv = EdgeServer(_one_model_app(prof), make_policy("LO-EDF"))
    assert srv.stats.profile_provenance == {"m": "profiled"}


def test_edge_server_backend_kwarg_runs_compiled_end_to_end():
    cfg = _reduced("mamba2-130m")
    be = CompiledBackend({"m": (cfg, 0)}, new_tokens=2)
    prof = be.profile("m", [0.9, 0.8])
    srv = EdgeServer(
        _one_model_app(prof), make_policy("SneakPeek"),
        backend=be, prompt_fn=_prompt_fn,
    )
    outs, stats = srv.run(_requests(8))
    assert stats.requests == 8
    assert stats.profile_provenance == {"m": "realized"}
    reports = [r for o in outs for r in o["reports"]]
    assert sum(r.batch_size for r in reports) == 8
    assert all(r.tokens.shape[1] == 2 for r in reports)
    with pytest.raises(ValueError):
        EdgeServer(_one_model_app(prof), make_policy("SneakPeek"),
                   executor=LMExecutor(backend=be), backend=be)


def test_edge_server_nondefault_backend_registers_true_footprints():
    cfg = _reduced("mamba2-130m")
    be = CompiledBackend({"m": (cfg, 0)}, new_tokens=2)
    prof = be.profile("m", [0.9, 0.8])
    srv = EdgeServer(
        _one_model_app(prof), make_policy("SneakPeek"),
        backend=be, prompt_fn=_prompt_fn,
        memory_capacity_bytes=10 * be.model_bytes("m"),
    )
    tl = srv.state.timeline(0)
    assert tl._profiles["m"] == be.model_bytes("m")


def test_edge_server_drift_stats_report_provenance():
    # A health-tracked pool over a costmodel-provenance profile: the
    # drift EWMA (realized_over_profiled) sits next to the provenance of
    # the estimate it corrects.
    be = SyntheticBackend({"m": (None, 0)}, new_tokens=2)
    prof = ModelProfile(name="m", recalls=[0.9, 0.8], latency_s=0.011,
                        latency_model=(0.01, 0.001), provenance="costmodel")
    workers = [Worker(wid=0, speed=1.0), Worker(wid=1, speed=1.0)]
    srv = EdgeServer(
        _one_model_app(prof), make_policy("SneakPeek"),
        executor=LMExecutor(backend=be), workers=workers,
        prompt_fn=_prompt_fn, health=True,
    )
    outs, stats = srv.run(_requests(8))
    assert stats.profile_provenance == {"m": "costmodel"}
    assert set(stats.realized_over_profiled) <= {0, 1}
    assert stats.realized_over_profiled  # drift observed on served lanes


def _held_moe():
    """granite-4.0-h-small at a small size, 2 of its 8 experts held."""
    import dataclasses

    return dataclasses.replace(_reduced("granite-4.0-h-small"), experts_here=2, expert_offset=2)


@pytest.mark.parametrize("backend", [CompiledBackend, ProfiledBackend])
def test_held_expert_counters_ride_the_one_readback(backend):
    """A hybrid SSM/attention model with held-expert MoE layers: still two
    host waits a forward, and each report carries the forward's counters,
    which are the model's own (prefill plus every decode step)."""
    import jax.numpy as jnp

    from repro.models import LM

    cfg = _held_moe()
    be = backend({"m": (cfg, 0)}, new_tokens=3)
    prompts = _prompts(4, vocab=cfg.vocab_size, seed=3)
    rep = be.run_batch("m", prompts, [0, 1, 2, 3])
    assert be.host_syncs == 2
    lm = LM(cfg)
    params = be._get("m")[1]
    _, cache, n = lm.prefill(params, jnp.asarray(prompts), max_len=11, counts=True)
    pairs, reached = int(n[0]), 0
    for j in range(2):
        _, cache, n = lm.decode_step(params, cache, jnp.asarray(rep.tokens[:, j:j + 1]),
                                     counts=True)
        pairs, reached = pairs + int(n[0]), reached + int(n[1])
    assert (rep.routed_pairs, rep.experts_reached) == (pairs, reached)
    assert 0 < reached <= 2 * 2 * cfg.num_layers
    assert (be.routed_pairs, be.experts_reached) == (pairs, reached)
    if backend is CompiledBackend:
        reps = be.run_batches("m", [prompts[:3], prompts[3:]], [[0, 1, 2], [3]])
        assert be.host_syncs == 4
        assert reps[0].routed_pairs > 0 and reps[1].routed_pairs == 0
        assert be.routed_pairs == pairs + reps[0].routed_pairs


def test_models_without_held_experts_report_no_counters():
    be = CompiledBackend({"m": (_reduced("mamba2-130m"), 0)}, new_tokens=3)
    rep = be.run_batch("m", np.ones((2, 8), np.int32), [0, 1])
    assert (rep.routed_pairs, rep.experts_reached, be.routed_pairs) == (0, 0, 0)
