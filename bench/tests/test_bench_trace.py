"""The trace reduction: busy time, idle share, top ops and named idle gaps."""
import time

import pytest

from bench import trace as tr

MS = 1e6  # ns


def _synthetic():
    # window 0..100 ms; device ops at [10, 30) and [25, 40) and [70, 80) ms
    return {
        "devices": {"/device:TPU:0": [("fusion.1", 10 * MS, 20 * MS), ("dot.2", 25 * MS, 15 * MS),
                                      ("fusion.1", 70 * MS, 10 * MS),
                                      ("late", 99 * MS, 5 * MS)]},
        "host": [("bench.window", 0.0, 100 * MS), ("bench.wait", 0.0, 8 * MS),
                 ("bench.close", 8 * MS, 90 * MS), ("bench.ingest", 41 * MS, 20 * MS),
                 ("bench.exec", 65 * MS, 20 * MS)],
    }


def test_reduce_by_hand():
    red = tr.reduce(_synthetic())
    # busy = [10, 40) + [70, 80) + [99, 100) = 41 ms of 100
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.041)
    assert red["idle_share"] == pytest.approx(0.59)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.030)
    assert ops["dot.2"] == pytest.approx(0.015)
    assert ops["late"] == pytest.approx(0.001)  # clipped to the window
    gaps = red["idle_gaps"]
    # gaps: [0,10) wait/close edge -> midpoint 5 in wait; [40,70) mid 55 in ingest;
    # [80,99) mid 89.5 in close (exec ended at 85)
    assert gaps[0] == ["bench.ingest", pytest.approx(0.030)]
    assert gaps[1] == ["bench.close", pytest.approx(0.019)]
    assert gaps[2] == ["bench.wait", pytest.approx(0.010)]


def test_ops_s_sums_every_op():
    t = _synthetic()
    # twelve more ops of 1 ms each, and a second run of one of them: past the top 10
    t["devices"]["/device:TPU:0"] += [(f"op.{i}", (41 + i) * MS, 1 * MS) for i in range(12)]
    t["devices"]["/device:TPU:0"].append(("op.11", 60 * MS, 2 * MS))
    red = tr.reduce(t)
    ops = red["ops_s"]
    assert len(ops) == 3 + 12 and len(red["device_ops"]) == 10
    assert ops["op.11"] == [pytest.approx(0.003), 2]
    assert ops["op.0"] == [pytest.approx(0.001), 1]
    assert ops["fusion.1"] == [pytest.approx(0.030), 2]
    assert ops["late"] == [pytest.approx(0.001), 1]  # clipped to the window
    assert sum(v[0] for v in ops.values()) == pytest.approx(0.030 + 0.015 + 0.001 + 0.014)
    assert {n for n, _ in red["device_ops"]} < set(ops)


def test_reduce_averages_over_devices():
    t = _synthetic()
    t["devices"]["/device:TPU:1"] = [("x", 0.0, 100 * MS)]
    red = tr.reduce(t)
    assert red["busy_s"] == pytest.approx((0.041 + 0.1) / 2)


def test_reduce_needs_the_window_span():
    t = _synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_recorded_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.exec"):
            jnp.ones((64, 64)).sum().block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    t = tr.load(tmp_path)
    names = {n for n, _, _ in t["host"]}
    assert {"bench.window", "bench.exec"} <= names
    red = tr.reduce(t)
    assert red["window_s"] >= 0.01
    assert 0.0 <= red["busy_s"] <= red["window_s"]
