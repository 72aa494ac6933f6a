#!/usr/bin/env python3
"""Knee sweep of a cell's offered rate, in whole requests per app per window.

    python3 bench/sweep.py --workload <name> --rates 2,4,6,8 --seconds 5

One process; each rate is one run of the cell with the traffic's
``per_app_per_window`` replaced (the compiled programs come from the cache
after the first).  Prints one JSON line per rate: attain, utility, the
latency percentiles, and whether the queue grew (the last window closed
more than one window late).  Needs the chip, like a run.
"""
import argparse
import copy
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="2,4,6,8")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=3_100_000_000)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    base = harness.load_cell(ROOT, args.workload)["traffic"]
    for rate in (int(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(base)
        traffic["per_app_per_window"] = rate
        try:
            res = harness.run(args.workload, args.seed + rate, args.seconds, False, root=ROOT,
                              cache_dir=ROOT / ".jax_cache", traffic_override=traffic)
        except harness.NoChip as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 1
        m = {k: v["value"] for k, v in res["metrics"].items()}
        per_s = rate * len(traffic["apps"]) / traffic["window_s"]
        print(json.dumps({"per_app_per_window": rate, "offered_per_s": per_s, **m,
                          "queue_grew": res["stream"]["last_close_late_ms"]
                          > 1e3 * traffic["window_s"], **res["stream"],
                          "correct": res["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
