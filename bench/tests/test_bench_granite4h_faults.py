"""Planted departures from granite-4.0-h-small's equations in the program,
run through the whole cell at a tiny size on the CPU: each turns
``correct`` false by the served-token gap."""
import dataclasses

import pytest

from bench import harness
from bench.archs import granitemoehybrid as g4
from bench.tests import tiny
from bench.tests.test_bench_granite4h import SEED, tiny_config


def _residual_multiplier_1(monkeypatch):
    orig = g4.model_config
    monkeypatch.setattr(g4, "model_config",
                        lambda d: dataclasses.replace(orig(d), residual_multiplier=1.0))


def _rope_on(monkeypatch):
    orig = g4.model_config
    monkeypatch.setattr(g4, "model_config",
                        lambda d: dataclasses.replace(orig(d), position_embedding="rope"))


def _shared_expert_dropped(monkeypatch):
    from repro.models import moe

    orig = moe.held_forward
    monkeypatch.setattr(moe, "held_forward", lambda p, x, cfg: orig(
        {k: v for k, v in p.items() if k != "shared"}, x, cfg))


@pytest.mark.parametrize("fault", [_residual_multiplier_1, _rope_on, _shared_expert_dropped],
                         ids=lambda f: f.__name__.strip("_"))
def test_planted_departure_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = harness.run("granite4h.paper", SEED, 0.3, False, require_tpu=False,
                      config_override=tiny_config(), traffic_override=tiny.traffic())
    assert not res["correct"], res["checks"]
    assert res["checks"]["gap_accurate"]["value"] > g4.GAP_LIMIT
