"""Every workload and metric named in BENCHMARK.json loads by name."""
import json
import re

import pytest

from bench.harness import ROOT, load_cell, load_module
from bench.models import arch, load_config

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_workload_loads(cell):
    spec = load_cell(ROOT, cell)
    assert spec["config_path"].is_file()
    gen = load_module(spec["generator"])
    assert callable(gen.windows) and callable(gen.training_sets)
    cfg = load_config(spec["config_path"])
    for app in spec["traffic"]["apps"]:
        rec = cfg["recalls"][app["name"]]
        assert len(rec["fast"]) == len(rec["accurate"]) == app["num_classes"]
    assert spec["per_layer"] and len(spec["end_to_end"]) >= 2


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_layout_matches_program(name):
    import jax

    from repro.models import LM

    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    cfg = load_config(ROOT / entry["file"])
    for key in entry["reduced"]:
        assert key in cfg["reduced"]
    for dims in cfg["roles"].values():
        mod = arch(dims)
        want = jax.tree.map(lambda a: a.shape, LM(mod.model_config(dims)).abstract_params())
        got = jax.tree.map(lambda t: t[0], mod.param_layout(dims),
                           is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[1], str))
        assert want == got


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_exists(metric):
    mod = load_module(ROOT / "bench" / "metrics" / f"{metric['name']}.py")
    assert callable(mod.read)
    for cell in metric.get("workloads", [w["name"] for w in BENCH["workloads"]]):
        reported = {m["name"] for m in load_cell(ROOT, cell)["end_to_end"]}
        assert metric["moves"] in reported and "setup_s" in reported


def test_names_follow_the_rules():
    names = [c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
