"""The reduction by the program's spans, and the readers of its metrics."""
import pytest

from bench import spans
from bench.harness import ROOT, load_module

MS = 1e6  # ns
DEV = "/device:TPU:0"


def _read(name, rec):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(rec)


def _synthetic(modules=True):
    # window 0: [0, 60) ms, with ingest, one forward (prefill [11, 21),
    # decode [21, 55)); window 1: [100, 150) ms, no ingest
    host = [
        ("serve.window", 0, 60, {"window": 0, "requests": 3}),
        ("serve.drain", 0, 1, {}), ("serve.ingest", 1, 6, {}), ("ingest.knn", 2, 5, {}),
        ("serve.select", 6, 8, {}), ("serve.commit", 8, 9, {}), ("serve.dispatch", 9, 59, {}),
        ("exec.forward", 10, 58, {"model": "m", "rows": 3, "padded": 4}),
        ("exec.prefill", 11, 21, {}), ("exec.decode", 21, 55, {}),
        ("serve.window", 100, 150, {"window": 1, "requests": 1}),
        ("serve.drain", 100, 101, {}), ("serve.select", 101, 105, {}),
        ("serve.commit", 105, 106, {}), ("serve.dispatch", 106, 140, {}),
        ("exec.forward", 107, 139, {"model": "m", "rows": 1, "padded": 1}),
        ("exec.prefill", 108, 113, {}), ("exec.decode", 113, 138, {}),
    ]
    ops = [("knn", 3, 4), ("pre", 12, 20), ("dec", 22, 30), ("dec", 31, 54), ("argmax", 54, 55),
           ("pre", 109, 112), ("dec", 114, 137)]
    mods = [("jit_knn_class_votes(1)", 3, 4), ("jit_prefill(2)", 12, 20),
            ("jit_decode_step(3)", 22, 30), ("jit_decode_step(3)", 31, 54),
            ("jit_prefill(2)", 109, 112), ("jit_decode_step(3)", 114, 137)]
    ms = [(n, s * MS, (e - s) * MS) for n, s, e in ops]
    return {
        "host": [(n, s * MS, (e - s) * MS, a) for n, s, e, a in host],
        "ops": {DEV: ms},
        "modules": {DEV: [(n, s * MS, (e - s) * MS) for n, s, e in mods]} if modules else {},
    }


def test_reduce_by_hand():
    red = spans.reduce(_synthetic())
    w0, w1 = red["windows"]
    assert (w0["window"], w0["requests"], w1["window"], w1["requests"]) == (0, 3, 1, 1)
    # idle in window 0: [0,3) [4,12) [20,22) [30,31) [55,60); window 1: [100,109) [112,114)
    # [137,150)
    assert w0["idle_s"] == pytest.approx(0.019)
    assert w1["idle_s"] == pytest.approx(0.024)
    assert (w0["select_s"], w1["select_s"]) == (pytest.approx(0.002), pytest.approx(0.004))
    # dispatch 50 - prefill 10 - decode 34; 34 - 5 - 25
    assert w0["dispatch_host_s"] == pytest.approx(0.006)
    assert w1["dispatch_host_s"] == pytest.approx(0.004)
    f0, f1 = red["forwards"]
    assert (f0["model"], f0["rows"], f0["padded"]) == ("m", 3, 4)
    assert f0["decode_dev_s"] == pytest.approx(0.031)  # the two decode modules, not argmax
    assert f1["decode_dev_s"] == pytest.approx(0.023)
    idle = red["idle_by_span"]
    want = {"serve.drain": 2, "serve.ingest": 2, "ingest.knn": 2, "serve.select": 6,
            "serve.commit": 2, "serve.dispatch": 4, "exec.forward": 6, "exec.prefill": 4,
            "exec.decode": 4, "serve.window": 11}
    assert idle == {k: pytest.approx(v * 1e-3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(0.043)
    line = spans.idle_line(red)
    assert line.startswith("spans: idle inside 2 closes") and "serve.window 11.000 ms" in line


def test_by_name_by_hand():
    t = _synthetic()
    t["host"].append(("route.experts", 22 * MS, 13 * MS, {"window": 0}))  # a new prefix
    by = spans.reduce(t)["by_name"]
    assert set(by) == {h[0] for h in t["host"]}
    assert by["route.experts"] == {"count": 1, "host_s": pytest.approx(0.013),
                                   "busy_s": pytest.approx(0.012)}  # [22, 30) and [31, 35)
    # [21, 55): ops 8 + 23 + 1 ms; [113, 138): 23 ms
    assert by["exec.decode"] == {"count": 2, "host_s": pytest.approx(0.059),
                                 "busy_s": pytest.approx(0.055)}
    assert by["serve.window"] == {"count": 2, "host_s": pytest.approx(0.110),
                                  "busy_s": pytest.approx(0.110 - 0.043)}
    t["ops"] = {}
    assert spans.reduce(t)["by_name"]["route.experts"]["busy_s"] is None


def test_no_module_events_no_decode_reading():
    red = spans.reduce(_synthetic(modules=False))
    assert [f["decode_dev_s"] for f in red["forwards"]] == [None, None]
    assert red["windows"][0]["idle_s"] == pytest.approx(0.019)  # the ops still count
    rec = {"windows": [], "spans": red}
    assert load_module(ROOT / "bench" / "metrics" / "decode_dev_ms.py").read(rec) is None


def test_no_program_spans():
    t = _synthetic()
    t["host"] = [h for h in t["host"] if h[0] != "serve.window"]
    assert spans.reduce(t) is None
    assert spans.median_ms(None, "idle_s") is None
    assert spans.idle_line(None) == "spans: no program spans in the trace"


def _rec(**kw):
    rec = {"windows": [{"late_s": 0.001, "queue_wait_s": 0.15, "queued": 3},
                       {"late_s": 0.002, "queue_wait_s": 0.06, "queued": 1}],
           "spans": spans.reduce(_synthetic()), "cold_forwards": 0}
    rec.update(kw)
    return rec


def test_readers_by_hand():
    rec = _rec()
    assert _read("close_idle_ms", rec) == pytest.approx(21.5)  # median of 19 and 24
    assert _read("select_ms", rec) == pytest.approx(3.0)
    assert _read("dispatch_host_ms", rec) == pytest.approx(5.0)
    assert _read("decode_dev_ms", rec) == pytest.approx(27.0)
    assert _read("queue_wait_ms", rec) == pytest.approx(52.5)  # 0.21 s over 4 requests
    assert _read("cold_forwards", rec) == 0


@pytest.mark.parametrize("name", ["close_idle_ms", "select_ms", "dispatch_host_ms",
                                  "decode_dev_ms", "queue_wait_ms", "cold_forwards"])
def test_a_program_without_the_spans_or_counters_gives_none(name):
    rec = {"windows": [{"late_s": 0.001}], "trace": {"idle_share": 0.5}}
    assert _read(name, rec) is None
    assert _read(name, dict(rec, spans=None)) is None


def test_recorded_program_spans_load(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tracing.enable(True)
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            with tracing.window(4) as w:
                w.set_metadata(requests=2)
                with tracing.span("serve.select"):
                    jnp.ones((64, 64)).sum().block_until_ready()
                with tracing.span("serve.dispatch"):
                    with tracing.span("exec.forward", model="m", rows=2, padded=2, rids=[1, 2]):
                        pass
    finally:
        tracing.enable(False)
    t = spans.load(tmp_path)
    names = {h[0] for h in t["host"]}
    assert {"serve.window", "serve.select", "serve.dispatch", "exec.forward"} <= names
    red = spans.reduce(t)
    (w,) = red["windows"]
    assert (w["window"], w["requests"]) == (4, 2)
    assert w["select_s"] > 0
    assert red["forwards"][0]["rows"] == 2
    if not t["ops"]:  # the CPU has no device plane: nothing to call idle
        assert w["idle_s"] is None and spans.median_ms(red, "idle_s") is None


def test_load_keeps_the_spans_that_carry_a_window(tmp_path):
    import jax

    from repro import tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tracing.enable(True)
    try:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            with tracing.window(2):
                with tracing.span("route.experts", layer=5):
                    pass
                with jax.profiler.TraceAnnotation("host.other"):
                    pass
    finally:
        tracing.enable(False)
    t = spans.load(tmp_path)
    assert {h[0] for h in t["host"]} == {"serve.window", "route.experts"}
    by = spans.reduce(t)["by_name"]
    assert by["route.experts"]["count"] == 1 and "host.other" not in by


def test_traced_run_hands_the_readers_spans_and_counters(monkeypatch):
    import jax

    from bench import harness, peaks
    from bench.tests import tiny

    # the CPU has no published peaks: lend it the chip's, for the readers that need them
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind, peaks.PEAKS["TPU v5 lite"])
    res = harness.run("granite8b.paper", 2**31 + 515, 1.5, True, require_tpu=False,
                      config_override=tiny.config(), traffic_override=tiny.traffic())
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["select_ms"] > 0 and m["dispatch_host_ms"] > 0
    assert m["queue_wait_ms"] > 0
    assert m["cold_forwards"] == 0
