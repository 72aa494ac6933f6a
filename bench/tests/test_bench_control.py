"""The float8 control of ``correct`` at a size a test run holds: on the same
sampled requests of a whole CPU run, the program (bf16) reads within the
limits of ``bench/check.py`` and the control above them, as it does at the
cells' own sizes on the chip (PERF.md)."""
from bench import check, harness
from bench.tests import tiny


def test_control_reads_above_the_program():
    res = harness.run("musicgen.paper", 2**31 + 77, 0.5, False, require_tpu=False,
                      config_override=tiny.config("sneakpeek-musicgen"),
                      traffic_override=tiny.traffic(4), control=True)
    assert res["correct"], res["checks"]
    assert "gap_accurate" in res["control"]["checks"]
    assert not res["control"]["correct"], res["control"]
    for name, c in res["checks"].items():
        if name.startswith("gap_"):
            assert c["value"] <= c["limit"] < res["control"]["checks"][name]["value"], name
