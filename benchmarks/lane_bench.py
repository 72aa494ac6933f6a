"""Lane-strategy benchmark: {sync, overlap} x {serial, thread, process}.

    PYTHONPATH=src python -m benchmarks.lane_bench [--quick]
        [--requests 1024] [--windows 16] [--workers 2] [--reps 5]
        [--lane serial,thread,process] [--mode sync,overlap | --overlap]
        [--out BENCH_lanes.json]

One identical multi-window request trace is served through the full
``EdgeServer`` loop under every (mode, lane) cell:

* **mode** — ``sync`` (today's serialized close: schedule, commit, then
  block on the lanes) vs ``overlap`` (``EdgeServer(overlap=True)``:
  window k+1 is drained and scheduled against a snapshot while window
  k's lanes execute, reconciled before its commit).
* **lane** — the ``ExecutorPool(lane=...)`` strategy: ``serial`` (lanes
  run one after another in the calling thread), ``thread`` (the default
  long-lived thread pool), ``process`` (spawned worker processes own the
  backends; forwards escape the GIL).

The substrate is ``SimulatedBackend`` with ``sleep`` occupancy: reports
always carry the profile's MODELLED seconds, so every cell makes
bit-identical scheduling decisions (asserted), while each batch really
occupies its lane for the modelled duration x ``time_scale``.  A
calibration pass picks ``time_scale`` so per-window execution wall time
is comparable to scheduling wall time — the regime where overlapping the
two phases matters (with execution either free or dominant, any loop
structure looks the same).

Per cell the artifact records total serve wall plus the sched/exec wall
breakdown (``ServeStats.sched_wall_s`` / ``exec_wall_s`` /
``overlap_saved_s``).  Process-lane workers are pre-spawned outside the
timed region (spawn cost is reported separately, not mixed into the
serving comparison).

Writes ``results/benchmarks/BENCH_lanes.json``.  Acceptance gate (armed
at >= 1024 requests/window x 2 workers): overlapped serving on the
thread lane must finish the same trace in >= 1.3x less wall time than
the synchronous loop.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import Worker, make_policy
from repro.data.applications import APP_SPECS, build_benchmark_suite, make_requests
from repro.serving import EdgeServer, LMExecutor, SimulatedBackend
from repro.serving.runtime import LANE_NAMES, require_cpu_platform

ROOT = Path(__file__).resolve().parents[1]
WINDOW_S = 0.1


def build_trace(n_per_window: int, n_windows: int, seed: int = 0):
    """``n_windows`` consecutive scheduling windows of ~``n_per_window``
    requests each (the single-window generator, shifted per window)."""
    reqs = []
    per_app = max(1, n_per_window // len(APP_SPECS))
    for w in range(n_windows):
        batch = make_requests(
            list(APP_SPECS.values()), per_app=per_app, window_s=WINDOW_S,
            mean_deadline_s=0.3, seed=seed + w, start_rid=len(reqs),
        )
        for r in batch:
            r.arrival_s += w * WINDOW_S
            r.deadline_s += w * WINDOW_S
        reqs.extend(batch)
    return reqs


def make_prompt_fn(vocab: int = 256, length: int = 8):
    """Per-rid deterministic prompts, cheap enough that prompt assembly
    does not dominate the execution phase (lanes call this concurrently)."""
    base = np.arange(length, dtype=np.int32)

    def prompt_fn(r):
        return (base + (r.rid * 2654435761) % vocab) % vocab
    return prompt_fn


def serve_cell(apps, sneaks, reqs, workers, *, lane: str, overlap: bool,
               time_scale: float, occupancy: str = "sleep"):
    """Serve the trace once under one (mode, lane) cell; returns the
    measurement row (wall breakdown + decision signature)."""
    profiles = {m.name: m for app in apps.values() for m in app.models}
    backend = SimulatedBackend(profiles, occupancy=occupancy,
                               time_scale=time_scale)
    executor = LMExecutor(backend=backend)
    spawn_s = 0.0
    with EdgeServer(
        apps, make_policy("SneakPeek"), executor=executor, sneakpeeks=sneaks,
        window_s=WINDOW_S, prompt_fn=make_prompt_fn(),
        workers=[Worker(i) for i in range(workers)],
        overlap=overlap, lane=lane,
    ) as srv:
        if lane == "process":
            # Pre-spawn the lane workers: process startup is a one-time
            # cost, reported separately from the serving comparison.
            t0 = time.perf_counter()
            for lane_exec in srv.pool.lanes.values():
                lane_exec.executor.backend._ensure()
            spawn_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        outs, stats = srv.run(list(reqs))
        wall = time.perf_counter() - t0
        decisions = hash(tuple(
            (e.request.rid, e.model, e.worker, e.order, e.batch_id)
            for o in outs for e in o["schedule"].sorted_entries()
        ))
    return {
        "mode": "overlap" if overlap else "sync",
        "lane": lane,
        "wall_s": wall,
        "sched_wall_s": stats.sched_wall_s,
        "exec_wall_s": stats.exec_wall_s,
        "overlap_saved_s": stats.overlap_saved_s,
        "spawn_s": spawn_s,
        "windows": stats.windows,
        "requests": stats.requests,
        "violations": stats.violations,
        "mean_utility": stats.mean_utility,
        "decisions": decisions,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny trace, no gate (CI smoke)")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests per window (default 1024; quick 64)")
    ap.add_argument("--windows", type=int, default=0,
                    help="number of scheduling windows (default 16; quick 2)")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--reps", type=int, default=0,
                    help="serve repetitions per cell, best wall kept "
                         "(default 5; quick 1)")
    ap.add_argument("--lane", type=str, default=",".join(LANE_NAMES),
                    help="comma list of lane strategies to run")
    ap.add_argument("--mode", type=str, default="sync,overlap",
                    help="comma list of loop modes to run")
    ap.add_argument("--overlap", action="store_true",
                    help="shorthand for --mode overlap")
    ap.add_argument(
        "--out", type=str,
        default=str(ROOT / "results" / "benchmarks" / "BENCH_lanes.json"),
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    n_req = args.requests or (64 if args.quick else 1024)
    n_win = args.windows or (2 if args.quick else 16)
    reps = args.reps or (1 if args.quick else 5)
    lanes = [s for s in args.lane.split(",") if s]
    for s in lanes:
        if s not in LANE_NAMES:
            raise SystemExit(f"unknown lane {s!r}; expected one of {LANE_NAMES}")
    modes = ["overlap"] if args.overlap else [m for m in args.mode.split(",") if m]
    for m in modes:
        if m not in ("sync", "overlap"):
            raise SystemExit(f"unknown mode {m!r}; expected sync or overlap")
    if "process" in lanes:
        require_cpu_platform("lane_bench --lane process")

    # Lane threads wake from many short modelled sleeps; with the default
    # 5 ms GIL switch interval each wake-up stalls behind whatever the
    # scheduling thread is doing, inflating execution wall time far past
    # the modelled occupancy.  A sub-millisecond interval keeps hand-offs
    # prompt so the measurement reflects the loop structure, not the
    # interpreter's arbitration latency.
    sys.setswitchinterval(5e-4)
    apps, sneaks = build_benchmark_suite(backend="numpy", seed=0)
    reqs = build_trace(n_req, n_win, seed=0)
    print(f"lane bench: {n_req} req/window x {n_win} windows x "
          f"{args.workers} workers; lanes={lanes} modes={modes} reps={reps}")

    # Calibration: a pure-control-plane pass (occupancy="none", so lanes
    # finish instantly) measures the scheduling wall and the modelled
    # per-lane busy seconds; pick time_scale so the busiest lane's real
    # occupancy lands near the scheduling wall — the regime where the
    # control plane and the lanes take comparable time, which is exactly
    # where overlapping the two phases matters.  The first pass pays
    # JIT/table-cache warmup no measured cell re-pays and is discarded;
    # the minimum over the following warm passes is the least-noise
    # estimate of the structural scheduling cost.
    serve_cell(apps, sneaks, reqs, args.workers, lane="thread",
               overlap=False, time_scale=0.0, occupancy="none")
    cals = [serve_cell(apps, sneaks, reqs, args.workers, lane="thread",
                       overlap=False, time_scale=0.0, occupancy="none")
            for _ in range(1 if args.quick else 3)]
    sched_wall = max(min(c["sched_wall_s"] for c in cals), 1e-6)
    probe_backend = SimulatedBackend(
        {m.name: m for app in apps.values() for m in app.models},
        occupancy="none")
    with EdgeServer(apps, make_policy("SneakPeek"),
                    executor=LMExecutor(backend=probe_backend),
                    sneakpeeks=sneaks, window_s=WINDOW_S,
                    prompt_fn=make_prompt_fn(),
                    workers=[Worker(i) for i in range(args.workers)]) as srv:
        _, pstats = srv.run(list(reqs))
    lane_busy = max(pstats.pool_busy_s.values()) if pstats.pool_busy_s else 0.0
    time_scale = sched_wall / lane_busy if lane_busy > 0 else 1.0
    print(f"calibration: sched wall {sched_wall*1e3:.1f} ms, busiest lane "
          f"{lane_busy:.3f} modelled s -> time_scale {time_scale:.4g}")

    rows = []
    for lane in lanes:
        # Best-of-``reps``: each rep serves the identical trace on a
        # fresh server; the minimum wall is the structural cost, the
        # spread is host noise (decisions are identical either way).
        # Reps INTERLEAVE the modes so a noisy stretch of host time hits
        # sync and overlap alike instead of biasing one cell.  The
        # process lane caps its reps: re-spawning workers per rep costs
        # seconds and the spawn is excluded from the timing anyway.
        lane_reps = min(reps, 2) if lane == "process" else reps
        trials = {m: [] for m in modes}
        for _ in range(lane_reps):
            for mode in modes:
                trials[mode].append(serve_cell(
                    apps, sneaks, reqs, args.workers, lane=lane,
                    overlap=(mode == "overlap"), time_scale=time_scale))
        for mode in modes:
            row = min(trials[mode], key=lambda r: r["wall_s"])
            row["wall_s_reps"] = [t["wall_s"] for t in trials[mode]]
            rows.append(row)
            print(f"  {row['mode']:>7} x {row['lane']:<7} wall "
                  f"{row['wall_s']*1e3:8.1f} ms  (sched {row['sched_wall_s']*1e3:7.1f}, "
                  f"exec {row['exec_wall_s']*1e3:7.1f}, saved "
                  f"{row['overlap_saved_s']*1e3:6.1f}; spawn {row['spawn_s']*1e3:6.1f})",
                  flush=True)

    # Decision identity: every cell served the identical trace and must
    # have made the identical decisions (same schedules, same utilities).
    sig0 = rows[0]
    failed = False
    for r in rows[1:]:
        same = (r["decisions"] == sig0["decisions"]
                and r["violations"] == sig0["violations"]
                and np.isclose(r["mean_utility"], sig0["mean_utility"],
                               rtol=1e-9, atol=1e-12))
        if not same:
            print(f"DECISION MISMATCH: {r['mode']} x {r['lane']} vs "
                  f"{sig0['mode']} x {sig0['lane']}")
            failed = True

    by = {(r["mode"], r["lane"]): r for r in rows}
    gate_ratio = None
    gate_armed = (n_req >= 1024 and args.workers == 2
                  and ("sync", "thread") in by and ("overlap", "thread") in by)
    if ("sync", "thread") in by and ("overlap", "thread") in by:
        gate_ratio = by[("sync", "thread")]["wall_s"] / by[("overlap", "thread")]["wall_s"]
    payload = {
        "benchmark": "lane_bench",
        "env": {
            "platform": platform.platform(),
            "python": platform.python_version(),
            "numpy": np.__version__,
        },
        "requests_per_window": n_req,
        "windows": n_win,
        "workers": args.workers,
        "reps": reps,
        "window_s": WINDOW_S,
        "time_scale": time_scale,
        "calibration_sched_wall_s": sched_wall,
        "calibration_lane_busy_s": lane_busy,
        "results": rows,
        "overlap_thread_speedup": gate_ratio,
        "gate_armed": gate_armed,
    }
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2, default=float))
    print(f"\nwrote {out}")
    if gate_ratio is not None:
        if gate_armed:
            status = "PASS" if gate_ratio >= 1.3 else "FAIL"
            print(f"overlap vs sync on thread lane: {gate_ratio:.2f}x "
                  f"(target >= 1.3x) [{status}]")
        else:
            print(f"overlap vs sync on thread lane: {gate_ratio:.2f}x "
                  f"(informational: gate arms at >=1024 requests x 2 workers)")
        if gate_armed and gate_ratio < 1.3:
            failed = True
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
