"""A whole run on the CPU at tiny sizes, past the look for a chip: sound,
``correct`` is true; with the timed path broken underneath, false."""
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2**31 + 4242


def _run():
    return harness.run("granite8b.paper", SEED, 0.3, False, require_tpu=False,
                       config_override=tiny.config(), traffic_override=tiny.traffic())


def _altered_tokens(monkeypatch):
    from repro.serving.backends import CompiledBackend

    orig = CompiledBackend._forward

    def forward(self, name, padded, class_token_ids):
        pf, dc, toks, preds = orig(self, name, padded, class_token_ids)
        return pf, dc, (toks + 1) % 512, preds

    monkeypatch.setattr(CompiledBackend, "_forward", forward)


def _half_batch(monkeypatch):
    from repro.serving.backends import CompiledBackend

    orig = CompiledBackend._forward

    def forward(self, name, padded, class_token_ids):
        cut = padded.copy()
        cut[padded.shape[0] // 2:] = 0  # the second half of the rows is never read
        return orig(self, name, cut, class_token_ids)

    monkeypatch.setattr(CompiledBackend, "_forward", forward)


def _state_unchanged(monkeypatch):
    from repro.models import LM

    orig = LM.decode_step

    def decode_step(self, params, cache, tokens):
        logits, _ = orig(self, params, cache, tokens)
        return logits, cache

    monkeypatch.setattr(LM, "decode_step", decode_step)


def _altered_votes(monkeypatch):
    from repro.core.sneakpeek import KNNSneakPeek

    orig = KNNSneakPeek._votes
    monkeypatch.setattr(KNNSneakPeek, "_votes",
                        lambda self, q: np.roll(np.asarray(orig(self, q)), 1, axis=1))


def _wrong_pick(monkeypatch):
    from repro.core.scheduler import SchedulerPolicy

    orig = SchedulerPolicy.schedule

    def schedule(self, requests, apps, now, state=None, arrays=None):
        sched = orig(self, requests, apps, now, state=state, arrays=arrays)
        first = sched.sorted_entries()[0]
        names = [m.name for m in apps[first.request.app].models]
        other = names[1 - names.index(first.model)]
        for e in sched.entries:  # the window's first batch runs on the other variant
            if e.batch_id == first.batch_id:
                e.model = other
        return sched

    monkeypatch.setattr(SchedulerPolicy, "schedule", schedule)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 3 * 2 * 3
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"attain", "utility", "p50_ms", "setup_s"}


@pytest.mark.parametrize("fault", [_altered_tokens, _half_batch, _state_unchanged,
                                   _altered_votes, _wrong_pick], ids=lambda f: f.__name__.strip("_"))
def test_broken_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
