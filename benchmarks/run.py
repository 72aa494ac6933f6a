"""Benchmark driver: every paper figure + kernels + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig5,...]

Writes JSON payloads to results/benchmarks/ and prints tables.  The
roofline section reads results/dryrun/ (built by repro.launch.dryrun)
and degrades gracefully when the dry-run matrix hasn't been compiled.
"""
from __future__ import annotations

import argparse
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="fewer seeds/sizes")
    ap.add_argument("--only", type=str, default="", help="comma list, e.g. fig5,kernels")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import paper_figs as pf
    from benchmarks.kernels import bench_kernels

    benches = {
        "fig5": pf.fig5_scheduling,
        "fig6": pf.fig6_estimation,
        "fig7": pf.fig7_incremental,
        "fig8": pf.fig8_required_accuracy,
        "fig9": pf.fig9_priors,
        "fig10": pf.fig10_deadlines,
        "fig11": pf.fig11_applications,
        "fig12": pf.fig12_arrival,
        "fig13": pf.fig13_penalty,
        "fig14": pf.fig14_heterogeneity,
        "fig15": pf.fig15_multiworker,
        "kernels": bench_kernels,
    }
    only = [s.strip() for s in args.only.split(",") if s.strip()]
    t0 = time.time()
    failures = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t = time.time()
        try:
            fn(quick=args.quick)
            print(f"[{name}] done in {time.time()-t:.1f}s", flush=True)
        except Exception as e:  # keep the suite running; report at the end
            failures.append((name, repr(e)))
            print(f"[{name}] FAILED: {e!r}", flush=True)

    # roofline table (reads dry-run artifacts if present)
    if not only or "roofline" in only:
        try:
            from benchmarks.roofline import main as roofline_main

            for mesh in ("pod", "multipod"):
                try:
                    sys.argv = ["roofline", "--mesh", mesh]
                    roofline_main()
                except Exception as e:
                    print(f"[roofline {mesh}] skipped: {e!r}")
        except Exception as e:
            print(f"[roofline] skipped: {e!r}")

    print(f"\nTotal: {time.time()-t0:.1f}s; failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
