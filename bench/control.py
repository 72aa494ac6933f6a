#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program and its float8 control.

    python3 bench/control.py --workload <name> --seeds 3 --seconds 3 [--first-seed N]

For each seed, in one process, one short run of the cell at its own load
(``harness.run``), and on the same sampled requests the float8 control of
``bench/reference.py``: the gap of the token the control puts first at each
position, put through the same limits as the program's.  Prints one line
per seed (``correct`` of the program and of the control) and, last, a JSON
summary: the largest program reading and the smallest control reading of
each number compared.
Needs the chip, like a run.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    prog, ctl = {}, {}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        try:
            res = harness.run(args.workload, seed, args.seconds, False, root=ROOT,
                              cache_dir=ROOT / ".jax_cache", control=True)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 1
        for k, c in res["checks"].items():
            prog.setdefault(k, []).append(c["value"])
        for k, c in res["control"]["checks"].items():
            ctl.setdefault(k, []).append(c["value"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": res["control"]["correct"],
                          "program": {k: c["value"] for k, c in res["checks"].items()},
                          "control": res["control"]["checks"], "metrics": {
                              k: m["value"] for k, m in res["metrics"].items()}}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": {k: max(v) for k, v in prog.items()},
                      "upper": {k: min(v) for k, v in ctl.items()},
                      "program": prog, "control": ctl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
