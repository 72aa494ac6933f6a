"""The traffic generator: the same seed gives the same stream, every seed
the same counts and shapes, and the same windows in another order."""
import json

import numpy as np

from bench.harness import ROOT, load_module

TRAFFIC = json.loads((ROOT / "bench" / "traffic" / "paper.json").read_text())
GEN = load_module(ROOT / "bench" / "generators" / f"{TRAFFIC['generator']}.py")
BIG_SEED = 2**31 + 12_345


def _flat(stream):
    return [(r["rid"], r["app"], r["due_s"], r["deadline_s"], r["label"],
             r["features"].tobytes(), r["prompt"].tobytes()) for w in stream for r in w]


def test_same_seed_same_stream():
    a = GEN.windows(TRAFFIC, BIG_SEED, 6, vocab=2048)
    b = GEN.windows(TRAFFIC, BIG_SEED, 6, vocab=2048)
    assert _flat(a) == _flat(b)


def test_other_seed_other_stream_same_shape():
    a = GEN.windows(TRAFFIC, BIG_SEED, 6, vocab=2048)
    b = GEN.windows(TRAFFIC, BIG_SEED + 1, 6, vocab=2048)
    assert _flat(a) != _flat(b)
    for wa, wb in zip(a, b):
        for app in TRAFFIC["apps"]:
            na = sum(r["app"] == app["name"] for r in wa)
            assert na == sum(r["app"] == app["name"] for r in wb) == TRAFFIC["per_app_per_window"]


def _contents(win):
    return sorted((r["app"], r["label"], round(r["due_s"] % TRAFFIC["window_s"], 12),
                   r["features"].tobytes()) for r in win)


def test_every_seed_serves_the_same_windows_in_another_order():
    lead = TRAFFIC["lead_in_windows"]
    a = GEN.windows(TRAFFIC, BIG_SEED, lead + 20, vocab=2048)
    b = GEN.windows(TRAFFIC, BIG_SEED + 1, lead + 20, vocab=2048)
    assert [_contents(w) for w in a[:lead]] == [_contents(w) for w in b[:lead]]
    ma, mb = [_contents(w) for w in a[lead:]], [_contents(w) for w in b[lead:]]
    assert sorted(ma) == sorted(mb) and ma != mb


def test_windows_times_and_fields():
    w_s, dl = TRAFFIC["window_s"], TRAFFIC["deadline_s"]
    stream = GEN.windows(TRAFFIC, 7, 4, vocab=100)
    rids = [r["rid"] for w in stream for r in w]
    assert sorted(rids) == list(range(len(rids)))
    dims = {a["name"]: a["feature_dim"] for a in TRAFFIC["apps"]}
    for k, win in enumerate(stream):
        dues = [r["due_s"] for r in win]
        assert dues == sorted(dues)
        for r in win:
            assert k * w_s <= r["due_s"] < (k + 1) * w_s
            assert np.isclose(r["deadline_s"] - r["due_s"], dl)
            assert r["features"].shape == (dims[r["app"]],)
            assert r["prompt"].shape == (TRAFFIC["prompt_tokens"],)
            assert 0 <= r["prompt"].min() and r["prompt"].max() < 100


def test_stream_label_frequencies():
    stream = GEN.windows(TRAFFIC, 3, 400, vocab=10)
    labels = [r["label"] for w in stream for r in w if r["app"] == "fall_detection"]
    assert abs(np.mean(np.asarray(labels) == 0) - 0.95) < 0.02


def test_training_sets_deterministic_and_uniform():
    a = GEN.training_sets(TRAFFIC, BIG_SEED, 600)
    b = GEN.training_sets(TRAFFIC, BIG_SEED, 600)
    for app in TRAFFIC["apps"]:
        xa, ya = a[app["name"]]
        xb, yb = b[app["name"]]
        assert np.array_equal(xa, xb) and np.array_equal(ya, yb)
        assert xa.shape == (600, app["feature_dim"])
        counts = np.bincount(ya, minlength=app["num_classes"])
        assert counts.min() > 600 / app["num_classes"] / 2

