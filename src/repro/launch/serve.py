"""Serving launcher: the paper's full pipeline on real LM variants.

    PYTHONPATH=src python -m repro.launch.serve --policy SneakPeek \
        --requests 24 --windows 3

Registers an "assistant" application whose variants are three reduced
LM architectures (mamba2 / tinyllama / gemma-7b families), with latency
profiles derived from the dry-run rooflines when `results/dryrun/`
exists (otherwise the analytic fallback), then streams synthetic
classification requests through the EdgeServer: SneakPeek stage ->
window queue -> scheduler -> LMExecutor (real prefill+decode).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--policy", default="SneakPeek",
                    choices=["MaxAcc-EDF", "LO-EDF", "LO-Priority", "Grouped", "SneakPeek"])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--windows", type=int, default=2)
    ap.add_argument("--deadline-ms", type=float, default=400.0)
    ap.add_argument("--new-tokens", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import ARCHS
    from repro.core import Application, ModelProfile, Request, make_policy
    from repro.serving import EdgeServer, LMExecutor
    from repro.serving.profiles import lm_latency_model

    rng = np.random.default_rng(args.seed)
    results_dir = Path(__file__).resolve().parents[3] / "results" / "dryrun"

    variant_archs = ["mamba2-130m", "tinyllama-1.1b", "gemma-7b"]
    recalls = {
        "mamba2-130m": [0.72, 0.70],
        "tinyllama-1.1b": [0.84, 0.82],
        "gemma-7b": [0.94, 0.92],
    }
    profiles, variants = [], {}
    for name in variant_archs:
        fixed, per_item = lm_latency_model(results_dir, name)
        cfg = ARCHS[name].reduced()
        profiles.append(ModelProfile(
            name=name, recalls=recalls[name],
            latency_s=fixed + per_item,
            load_latency_s=2 * ARCHS[name].param_count() / 25e9 / 16,
            latency_model=(fixed, per_item),
        ))
        variants[name] = (cfg, hash(name) % 100)
        print(f"variant {name:16s} l(m)={fixed+per_item:8.4f}s "
              f"load={profiles[-1].load_latency_s:7.3f}s "
              f"({'roofline' if results_dir.exists() else 'analytic'} profile)")

    app = Application(name="assistant", models=profiles, penalty="sigmoid")
    executor = LMExecutor(variants, new_tokens=args.new_tokens)
    vocab = variants["mamba2-130m"][0].vocab_size

    def prompt_fn(req):
        return rng.integers(0, vocab, 12).astype(np.int32)

    server = EdgeServer({"assistant": app}, make_policy(args.policy),
                        executor=executor, prompt_fn=prompt_fn)
    horizon = args.windows * server.queue.window_s
    reqs = [
        Request(rid=i, app="assistant",
                arrival_s=float(rng.uniform(0, horizon)),
                deadline_s=float(rng.uniform(0, horizon) + args.deadline_ms / 1e3),
                true_label=int(rng.integers(2)))
        for i in range(args.requests)
    ]
    outs, stats = server.run(reqs, horizon_s=horizon)
    print(f"\npolicy={args.policy} windows={stats.windows} requests={stats.requests}")
    print(f"mean utility {stats.mean_utility:.3f} | violations {stats.violations} | "
          f"swaps {stats.swaps} | sched overhead {stats.scheduling_overhead_s*1e3:.1f} ms")
    for o in outs:
        for rep in o["reports"] or []:
            print(f"  batch[{rep.model:16s}] size={rep.batch_size:2d} "
                  f"prefill={rep.prefill_s*1e3:7.1f}ms decode={rep.decode_s*1e3:7.1f}ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
