"""Each per-layer metric reader on a synthetic record."""
import pytest

from bench import flops
from bench.harness import ROOT, load_module
from bench.tests.tiny import SSD, TRANSFORMER

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(name, rec):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read(rec)


def _rec(**kw):
    rec = {
        "windows": [{"late_s": 0.001, "ingest_s": 0.002, "sched_s": 0.005},
                    {"late_s": 0.003, "ingest_s": 0.004, "sched_s": 0.007}],
        "forwards": [{"model": "t", "rows": 3, "padded": 4, "seq": 8, "prefill_s": 0.010,
                      "decode_s": 0.030},
                     {"model": "s", "rows": 1, "padded": 1, "seq": 8, "prefill_s": 0.002,
                      "decode_s": 0.006}],
        "requests": [{"model": "t"}, {"model": "t"}, {"model": "t"}, {"model": "s"}],
        "roles": {"fast": SSD, "accurate": TRANSFORMER},
        "dims_by_model": {"t": TRANSFORMER, "s": SSD},
        "prompt_len": 8, "new_tokens": 4, "peaks": PEAKS,
        "trace": {"busy_s": 0.4, "window_s": 2.0, "idle_share": 0.8},
    }
    rec.update(kw)
    return rec


def test_host_clock_readers():
    rec = _rec()
    assert _read("close_late_ms", rec) == pytest.approx(2.0)
    assert _read("ingest_ms", rec) == pytest.approx(3.0)
    assert _read("sched_ms", rec) == pytest.approx(3.0)
    assert _read("prefill_ms", rec) == pytest.approx(6.0)
    assert _read("decode_ms", rec) == pytest.approx(18.0)
    assert _read("accurate_share", rec) == pytest.approx(75.0)
    assert _read("idle_share", rec) == pytest.approx(80.0)


def test_decode_roofline_and_mfu():
    rec = _rec()
    least = 0.0
    for m, b in (("t", 4), ("s", 1)):
        d = rec["dims_by_model"][m]
        for j in range(3):
            least += flops.least_time(*flops.decode_step_cost(d, b, 8 + j + 1), PEAKS)[0]
    assert _read("decode_roofline", rec) == pytest.approx(100 * least / 0.036)
    ops = flops.forward_flops(TRANSFORMER, 3, 8, 4) + flops.forward_flops(SSD, 1, 8, 4)
    assert _read("mfu", rec) == pytest.approx(100 * ops / (0.048 * 197e12))


@pytest.mark.parametrize("name", ["close_late_ms", "ingest_ms", "sched_ms", "accurate_share",
                                  "prefill_ms", "decode_ms", "decode_roofline", "mfu",
                                  "idle_share"])
def test_nothing_to_read_gives_none(name):
    rec = _rec(windows=[], forwards=[], requests=[], trace=None)
    assert _read(name, rec) is None
