"""Open-loop windowed sensor traffic (the paper's testbed, extended to a stream).

Every window of ``window_s`` seconds holds exactly ``per_app_per_window``
requests of each application, at uniform times inside the window, so every
seed gives the same counts and the same shapes in another order.  A request
carries its sensor features (a draw from the application's Gaussian
mixture, its label from the stream's label frequencies), a deadline
``deadline_s`` after arrival, and ``prompt_tokens`` prompt ids.

The class geometry of each application is fixed by its name, as in the
program's ``data/applications.py`` (copied here so the yardstick cannot
move): unit class directions scaled by ``class_sep``, seeded by the CRC32 of
the name.  The k-NN training set of the SneakPeek stage is drawn from the
same mixture with uniform labels, from the configuration's own seed: the
deployment's k-NN model stays the same from run to run.  The windows'
contents come from the mix's ``window_pool_seed`` and the run's seed orders
them and draws the prompts: every seed gives the same work, in another
order.
"""
from __future__ import annotations

import zlib

import numpy as np


def class_means(app: dict) -> np.ndarray:
    """(num_classes, feature_dim) class means of an application."""
    rng = np.random.default_rng(zlib.crc32(app["name"].encode()) % (2**32))
    means = rng.normal(size=(app["num_classes"], app["feature_dim"]))
    means /= np.linalg.norm(means, axis=1, keepdims=True)
    return means * app["class_sep"]


def _draw(app: dict, n: int, rng: np.random.Generator, freqs) -> tuple[np.ndarray, np.ndarray]:
    p = np.asarray(freqs, np.float64)
    labels = rng.choice(app["num_classes"], size=n, p=p / p.sum())
    feats = class_means(app)[labels] + rng.normal(size=(n, app["feature_dim"]))
    return feats.astype(np.float32), labels.astype(np.int32)


def training_sets(traffic: dict, seed: int, n: int) -> dict:
    """{app: (features, labels)}: ``n`` uniform-label draws per application."""
    out = {}
    for i, app in enumerate(traffic["apps"]):
        rng = np.random.default_rng([int(seed), 1, i])
        out[app["name"]] = _draw(app, n, rng, np.ones(app["num_classes"]))
    return out


def windows(traffic: dict, seed: int, n_windows: int, vocab: int) -> list[list[dict]]:
    """Requests of each window, in due order; times in seconds from stream start.

    Every window's contents (labels, features, arrival offsets inside the
    window) come from the mix's ``window_pool_seed``; the run's seed orders
    the windows after the lead-in and draws the prompts, so every seed
    serves the same work in another order."""
    w_s, per, dl = traffic["window_s"], traffic["per_app_per_window"], traffic["deadline_s"]
    rng = np.random.default_rng([int(traffic["window_pool_seed"]), 2])
    contents = [[(app, *_draw(app, per, rng, app["stream_freqs"]),
                  np.sort(rng.uniform(0.0, w_s, size=per))) for app in traffic["apps"]]
                for _ in range(n_windows)]
    run = np.random.default_rng([int(seed), 4])
    lead = min(traffic["lead_in_windows"], n_windows)
    contents = contents[:lead] + [contents[lead + i] for i in run.permutation(n_windows - lead)]
    out, rid = [], 0
    for w, win in enumerate(contents):
        batch = []
        for app, feats, labels, offsets in win:
            prompts = run.integers(0, vocab, size=(per, traffic["prompt_tokens"]), dtype=np.int32)
            for i in range(per):
                due = w * w_s + float(offsets[i])
                batch.append({
                    "rid": rid, "app": app["name"], "due_s": due, "deadline_s": due + dl,
                    "features": feats[i], "label": int(labels[i]), "prompt": prompts[i],
                })
                rid += 1
        out.append(sorted(batch, key=lambda r: (r["due_s"], r["rid"])))
    return out
