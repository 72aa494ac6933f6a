"""The plain scheduler of ``bench/scheduler_ref.py`` against the program's own
serving loop, window after window with the worker's queue carried: the same
requests, votes and profiles give the same (request, variant, order, batch)
decisions, on latencies at which the accuracy-deadline trade binds."""
import json

import numpy as np
import pytest

from bench import scheduler_ref
from bench.harness import ROOT, load_module

TRAFFIC = json.loads((ROOT / "bench" / "traffic" / "paper.json").read_text())
CONFIG = json.loads((ROOT / "bench" / "configs" / "sneakpeek-granite8b.json").read_text())
GEN = load_module(ROOT / "bench" / "generators" / "open_loop.py")
# (name, latency_s, load_s, bytes, (fixed_s, per_item_s)) of the fast and the accurate variant
FAST = ("fast", 0.0072, 0.02, 400, (0.0058, 0.0007))
ACCURATE = ("accurate", 0.0503, 0.05, 8000, (0.0499, 0.0004))


def _program(penalty, capacity, fastpath):
    from repro.core import make_policy
    from repro.core.accuracy import ModelProfile
    from repro.core.dirichlet import jeffreys_prior
    from repro.core.types import Application
    from repro.serving import EdgeServer

    apps = {}
    for app in TRAFFIC["apps"]:
        rec = CONFIG["recalls"][app["name"]]
        apps[app["name"]] = Application(
            name=app["name"], penalty=penalty, prior=jeffreys_prior(app["num_classes"]),
            models=[ModelProfile(n, rec[role], lat, load, size, lm, provenance="realized")
                    for role, (n, lat, load, size, lm) in (("fast", FAST), ("accurate", ACCURATE))])
    server = EdgeServer(apps, make_policy("SneakPeek", fastpath=fastpath),
                        window_s=TRAFFIC["window_s"], memory_capacity_bytes=capacity)
    return apps, server


def _replay(penalty, capacity, fastpath, seed, n_windows=150):
    from repro.core.dirichlet import posterior_mean_batch
    from repro.core.types import Request

    apps, server = _program(penalty, capacity, fastpath)
    variants = {a: [scheduler_ref.Variant(m.name, m.recalls, m.latency_s, m.load_latency_s,
                                          m.memory_bytes, m.latency_model) for m in app.models]
                for a, app in apps.items()}
    sizes = {n: size for n, _, _, size, _ in (FAST, ACCURATE)}
    ref = scheduler_ref.Scheduler(variants, {a: penalty for a in apps}, capacity, sizes)
    rng = np.random.default_rng(seed)
    stats = {"differ": 0, "decisions": 0, "exact": 0, "greedy": 0, "models": set()}
    for w, win in enumerate(GEN.windows(TRAFFIC, seed, n_windows, 512)):
        now = (w + 1) * TRAFFIC["window_s"] + rng.uniform(0.0, 0.004)
        mine = []
        # every other window on average, each application's votes agree on
        # one label, so the window holds three groups and is scheduled exactly
        agree = {a: rng.integers(app.num_classes) for a, app in apps.items()
                 } if rng.random() < 0.5 else {}
        for r in win:
            c = apps[r["app"]].num_classes
            top = agree.get(r["app"], rng.integers(c))
            votes = rng.multinomial(5, np.eye(c)[top] * 0.7 + 0.3 / c).astype(float)
            if r["app"] in agree:
                votes = 5.0 * np.eye(c)[top]
            theta = posterior_mean_batch(apps[r["app"]].prior, votes[None])[0]
            server.submit(Request(r["rid"], r["app"], r["due_s"], r["deadline_s"],
                                  evidence=votes, theta=theta))
            mine.append(scheduler_ref.Req(r["rid"], r["app"], r["due_s"], r["deadline_s"], votes))
        sched = server.run_window(now)["schedule"]
        program = [(e.request.rid, e.model, e.order, e.batch_id) for e in sched.sorted_entries()]
        n_groups = len(ref.groups(sorted(mine, key=lambda r: (r.arrival_s, r.rid))))
        stats["exact" if n_groups <= scheduler_ref.TAU else "greedy"] += 1
        decisions = ref.window(mine, now)
        stats["differ"] += scheduler_ref.differing(program, decisions)
        stats["decisions"] += len(decisions)
        stats["models"].update(d[1] for d in decisions)
    return stats


@pytest.mark.parametrize("penalty", ["sigmoid", "linear", "step"])
@pytest.mark.parametrize("capacity", [10_000, 8_200], ids=["both_fit", "one_fits"])
def test_reference_decides_as_the_program(penalty, capacity):
    stats = _replay(penalty, capacity, fastpath=True, seed=2**31 + 17)
    assert stats["differ"] == 0, stats
    assert stats["decisions"] == 150 * 12
    assert stats["models"] == {"fast", "accurate"}, stats
    assert stats["exact"] > 0 and stats["greedy"] > 0, stats


def test_reference_decides_as_the_scalar_path():
    stats = _replay("step", 10_000, fastpath=False, seed=2**31 + 29, n_windows=60)
    assert stats["differ"] == 0, stats


def test_a_changed_pick_is_counted():
    prog = [(1, "a", 1, 0), (2, "a", 2, 0), (3, "b", 3, 1)]
    assert scheduler_ref.differing(prog, prog) == 0
    assert scheduler_ref.differing(prog, [(1, "b", 1, 0)] + prog[1:]) == 1
    assert scheduler_ref.differing(prog, prog[:2]) == 1
    assert scheduler_ref.differing(prog, [(2, "a", 1, 0), (1, "a", 2, 0), prog[2]]) == 2
