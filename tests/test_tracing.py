"""Program spans on the profiler's clock (``repro.tracing``) and the
serving counters they come with (queue wait, cold forwards)."""
import jax
import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.core import Application, ModelProfile, Request, Worker, make_policy
from repro.core.dirichlet import jeffreys_prior
from repro.core.sneakpeek import KNNSneakPeek
from repro.serving import (
    CompiledBackend,
    EdgeServer,
    ExecutorPool,
    LMExecutor,
    SimulatedBackend,
)

PROGRAM = ("serve.", "ingest.", "exec.")


def _prompt_fn(req):
    return (np.arange(8, dtype=np.int32) + int(req.rid)) % 64


def _requests(n, spacing=0.03):
    rng = np.random.default_rng(0)
    return [Request(rid=i, app="app", arrival_s=spacing * (i + 1), deadline_s=10.0,
                    features=rng.normal(size=4).astype(np.float32), true_label=i % 2)
            for i in range(n)]


def _knn():
    rng = np.random.default_rng(1)
    return {"app": KNNSneakPeek(rng.normal(size=(40, 4)), np.arange(40) % 2, 2, k=3,
                                backend="numpy")}


def _spans(path):
    """(name, start_ns, end_ns, args) of the program's host spans."""
    from jax.profiler import ProfileData

    files = sorted(path.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def _traced(path, fn):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tracing.enable(True)
    try:
        with jax.profiler.trace(str(path), profiler_options=opts):
            out = fn()
    finally:
        tracing.enable(False)
    return out, _spans(path)


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.fixture(scope="module")
def model_cfg():
    return get_config("mamba2-130m").reduced()


def test_off_returns_the_shared_null_context():
    assert tracing.span("serve.drain") is tracing.NULL
    assert tracing.span("exec.forward", model="m", rids=[1, 2]) is tracing.NULL
    assert tracing.window(3) is tracing.NULL
    fn = _prompt_fn
    assert tracing.carry(fn) is fn
    with tracing.window(0) as w:
        assert w is None
        tracing.NULL.set_metadata(requests=1)  # a no-op while off


def test_on_spans_carry_their_window(tmp_path):
    def body():
        with tracing.window(7) as w:
            w.set_metadata(requests=2)
            with tracing.span("exec.forward", rids=[[1, 2], [3]]):
                pass
        with tracing.span("serve.drain"):
            pass

    _, spans = _traced(tmp_path, body)
    by = {s[0]: s for s in spans}
    assert by["serve.window"][3] == {"window": 7, "requests": 2}
    assert by["exec.forward"][3]["window"] == 7
    assert by["exec.forward"][3]["rids"] == "1 2 3"
    assert _inside(by["exec.forward"], by["serve.window"])
    assert by["serve.drain"][3]["window"] == -1  # outside any close


def test_server_span_tree_per_window(tmp_path, model_cfg):
    be = CompiledBackend({"m": (model_cfg, 0)}, new_tokens=3)
    app = {"app": Application(name="app", models=[be.profile("m", [0.9, 0.8])],
                              penalty="sigmoid", prior=jeffreys_prior(2))}
    srv = EdgeServer(app, make_policy("SneakPeek"), sneakpeeks=_knn(), backend=be,
                     prompt_fn=_prompt_fn)
    reqs = _requests(9)
    (outs, stats), spans = _traced(tmp_path, lambda: srv.run(reqs))
    wins = sorted((s for s in spans if s[0] == "serve.window"), key=lambda s: s[1])
    assert [s[3]["window"] for s in wins] == list(range(len(wins))) == list(range(3))
    assert sum(s[3]["requests"] for s in wins) == len(reqs)
    for win in wins:
        w = win[3]["window"]
        mine = [s for s in spans if s[3].get("window") == w and s[0] != "serve.window"]
        assert all(_inside(s, win) for s in mine)
        names = [s[0] for s in mine]
        for name in ("serve.drain", "serve.ingest", "ingest.knn", "serve.select",
                     "serve.commit", "serve.dispatch", "exec.forward", "exec.prefill",
                     "exec.decode"):
            assert name in names, (w, name)
        one = {n: next(s for s in mine if s[0] == n) for n in names}
        assert _inside(one["ingest.knn"], one["serve.ingest"])
        assert _inside(one["exec.forward"], one["serve.dispatch"])
        # the window's same-model batches run as one fused forward
        fwds = [s for s in mine if s[0] == "exec.forward"]
        assert len(fwds) == 1
        assert fwds[0][3]["model"] == "m"
        assert fwds[0][3]["rows"] == win[3]["requests"]
        assert fwds[0][3]["padded"] >= fwds[0][3]["rows"]
        rids = sorted(int(x) for x in str(fwds[0][3]["rids"]).split())
        assert rids == sorted(e.request.rid for e in outs[w]["schedule"])
        pre, dec = one["exec.prefill"], one["exec.decode"]
        assert _inside(pre, fwds[0]) and _inside(dec, fwds[0]) and pre[2] <= dec[1]
    assert stats.windows == 3


def test_forward_span_carries_its_host_syncs(tmp_path, model_cfg):
    be = CompiledBackend({"m": (model_cfg, 0)}, new_tokens=3)
    p = np.zeros((3, 8), np.int32)

    def body():
        be.run_batch("m", p, [0, 1, 2])
        be.run_batches("m", [p, p[:1]], [[0, 1, 2], [3]])
        be.run_batch("m", p, [0, 1, 2], class_token_ids=np.array([1, 2]))

    _, spans = _traced(tmp_path, body)
    fwds = sorted((s for s in spans if s[0] == "exec.forward"), key=lambda s: s[1])
    assert [s[3]["syncs"] for s in fwds] == [2, 2, 2]
    assert be.host_syncs == 6
    for name in ("exec.prefill", "exec.decode"):
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == 3 and all(any(_inside(s, f) for f in fwds) for s in inner)


def test_overlapped_pool_lanes_carry_the_dispatching_window(tmp_path, model_cfg):
    be = CompiledBackend({"m": (model_cfg, 0)}, new_tokens=2)
    app = {"app": Application(name="app", models=[be.profile("m", [0.9, 0.8])],
                              penalty="sigmoid", prior=jeffreys_prior(2))}
    pool = ExecutorPool([Worker(0), Worker(1)], backend_factory=be.spawn)
    reqs = _requests(9)

    def serve():
        with EdgeServer(app, make_policy("SneakPeek"), executor=pool, workers=[Worker(0),
                        Worker(1)], prompt_fn=_prompt_fn, overlap=True) as srv:
            return srv.run(reqs)

    (outs, _), spans = _traced(tmp_path, serve)
    fwds = [s for s in spans if s[0] == "exec.forward"]
    by_window = {}
    for s in fwds:
        by_window.setdefault(s[3]["window"], []).extend(int(x) for x in str(s[3]["rids"]).split())
    # every forward carries the window whose close scheduled its requests
    assert len(outs) == 3
    for w, o in enumerate(outs):
        assert sorted(by_window[w]) == sorted(e.request.rid for e in o["schedule"])


# ------------------------------------------------------------ counters


PROFILES = {"small": ModelProfile("small", recalls=[0.74, 0.72], latency_s=0.010)}


@pytest.mark.parametrize("overlap", [False, True])
def test_queue_wait_against_a_hand_computed_stream(overlap):
    arrivals = [0.01, 0.03, 0.12, 0.15, 0.25]
    reqs = [Request(rid=i, app="lm", arrival_s=a, deadline_s=a + 0.3, true_label=0)
            for i, a in enumerate(arrivals)]
    app = {"lm": Application(name="lm", models=list(PROFILES.values()), penalty="sigmoid")}
    kw = {"workers": [Worker(0)], "overlap": True} if overlap else {}
    with EdgeServer(app, make_policy("LO-EDF"), prompt_fn=_prompt_fn,
                    executor=LMExecutor(backend=SimulatedBackend(PROFILES)), **kw) as srv:
        _, stats = srv.run(reqs)
    # closes at 0.1, 0.2, 0.3: (0.09 + 0.07) + (0.08 + 0.05) + 0.05
    assert stats.queued == 5
    assert stats.queue_wait_s == pytest.approx(0.34)


def test_cold_forwards_count_first_seen_shapes_only(model_cfg):
    be = CompiledBackend({"m": (model_cfg, 0)}, new_tokens=2, seq_multiple=8)
    p = np.zeros((3, 8), np.int32)
    be.run_batch("m", p, [0, 1, 2])                      # (4, 8): cold
    be.run_batch("m", p, [0, 1, 2])                      # warm
    be.run_batch("m", np.zeros((4, 8), np.int32), list(range(4)))  # same padded shape
    assert be.cold_forwards == 1
    be.run_batch("m", np.zeros((1, 8), np.int32), [0])   # (1, 8): cold
    be.run_batches("m", [np.zeros((1, 8), np.int32)] * 2, [[0], [1]])  # (2, 8): cold
    be.run_batch("m", np.zeros((1, 9), np.int32), [0])   # (1, 16): cold
    be.run_batch("m", np.zeros((1, 9), np.int32), [0])
    assert be.cold_forwards == 4


def test_serve_stats_copy_cold_forwards(model_cfg):
    be = CompiledBackend({"m": (model_cfg, 0)}, new_tokens=2)
    app = {"app": Application(name="app", models=[be.profile("m", [0.9, 0.8])],
                              penalty="sigmoid", prior=jeffreys_prior(2))}
    before = be.cold_forwards  # the latency calibration's shapes
    srv = EdgeServer(app, make_policy("SneakPeek"), backend=be, prompt_fn=_prompt_fn)
    _, stats = srv.run(_requests(6))
    assert stats.cold_forwards == be.cold_forwards > before
    pool = ExecutorPool([Worker(0), Worker(1)], backend_factory=be.spawn)
    pool.lanes[0].executor.backend.cold_forwards = 2
    pool.lanes[1].executor.backend.cold_forwards = 3
    assert pool.cold_forwards == 5
    assert not hasattr(stats, "wall_s")
