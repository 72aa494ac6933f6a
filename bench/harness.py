"""One run of one cell: set-up, the open-loop window, the checks, the result.

Everything a cell needs is found by name: the workload in ``BENCHMARK.json``
names a configuration (``bench/configs/<file>``) and a traffic mix
(``bench/traffic/<traffic>.json``), each model group of the configuration
names its architecture (``bench/archs/<arch>.py``), the mix names its
generator (``bench/generators/<generator>.py``), and each per-layer metric
has its reader (``bench/metrics/<name>.py``), which reads the record the
run leaves: its windows and forwards, the profiler trace's device ops
(``bench/trace.py``) and the program's spans (``bench/spans.py``).

The load loop is the benchmark's own.  Requests are due on a seeded
schedule; at every tick of ``window_s`` on the wall clock the loop hands
the window's requests to ``EdgeServer.submit`` and closes the window with
``EdgeServer.run_window(now)``, ``now`` being the real seconds since the
stream started.  A request completes when the forward that served it
returned; its latency runs from when it was due.  A few untimed windows
come first, then the measured ones; the run ends when every request due in
the measured window has been served (a request never served is ``failed``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import scheduler_ref, stalls

ROOT = Path(__file__).resolve().parents[1]
# The profiler traces the last windows of the measured window (1 s of the
# paper's traffic), with Python function tracing off; the host-clock
# per-layer metrics of a traced run come from the windows before it.
TRACE_WINDOWS = 10


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str) -> dict:
    """The workload entry of ``BENCHMARK.json`` with its configuration,
    traffic, generator and metric entries resolved by name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell, "config_entry": config, "config_path": root / config["file"],
        "traffic": traffic,
        "generator": root / "bench" / "generators" / f"{traffic['generator']}.py",
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
        "metric_dir": root / "bench" / "metrics",
    }


class Log:
    """Progress lines on standard error, each naming the device."""

    def __init__(self):
        self.tag = "[no device]"

    def __call__(self, msg: str) -> None:
        print(f"{self.tag} {msg}", file=sys.stderr, flush=True)


log = Log()


def _device(require_tpu: bool, chips: int) -> dict:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    log.tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    if require_tpu and dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {dev['count']}")
    return dev


class _Events:
    """Counts of compilations and persistent-cache hits (JAX monitoring)."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


_EVENTS = None


def _events() -> _Events:
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = _Events()
    return _EVENTS


def _holdout_split(x, y, num_classes: int, frac: float, seed: int):
    """The training rows ``KNNSneakPeek`` keeps after its held-out slice."""
    perm = np.random.default_rng(seed).permutation(len(x))
    n_hold = max(num_classes, int(len(x) * frac))
    return x[perm[n_hold:]], y[perm[n_hold:]]


def replay_decisions(decided, sneaks, variants, penalty: str, capacity) -> tuple[int, int]:
    """(decisions, differing ones): every window the program closed, replayed
    through ``scheduler_ref`` on the same requests and the k-NN votes that
    the ingest comparison checks, its worker queue carried by its own picks."""
    votes = {}
    for sp in sneaks.values():
        for feats, v in sp.calls:
            for f, row in zip(np.asarray(feats), np.asarray(v)):
                votes[f.tobytes()] = row
    sizes = {v.name: v.size for vs in variants.values() for v in vs}
    ref = scheduler_ref.Scheduler(variants, {a: penalty for a in variants}, capacity, sizes)
    n = diff = 0
    for now, win, program in decided:
        reqs = []
        for r in win:
            v = votes.get(np.asarray(r["features"], np.float32).tobytes())
            if v is None:  # never ingested: the reference reads the prior alone
                diff += 1
                v = np.zeros(len(variants[r["app"]][0].recalls))
            reqs.append(scheduler_ref.Req(r["rid"], r["app"], r["due_s"], r["deadline_s"], v))
        mine = ref.window(reqs, now)
        n += len(mine)
        diff += scheduler_ref.differing(program, mine)
    return n, diff


def _peak_bytes(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run(workload: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT,
        require_tpu: bool = True, cache_dir: Path | None = None, t_start: float | None = None,
        config_override: dict | None = None, traffic_override: dict | None = None,
        control: bool = False) -> dict:
    """One run; returns the result object (``checks`` as its last key).

    ``control=True`` also reads the float8 control on the same sampled
    requests and puts its gaps through the same limits
    (``result["control"]``: its ``checks`` and ``correct``); the benchmark's
    own runs leave it off."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_cell(root, workload)
    dev = _device(require_tpu, spec["cell"]["chips"])
    import jax

    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    ev = _events()

    from bench import check, flops, spans
    from bench.models import arch, load_config
    from bench.serving import StampedBackend, TimedKNN, span
    from bench.utility import realized_utility
    from bench.weights import make_weights
    from repro import tracing
    from repro.core import make_policy
    from repro.core.dirichlet import jeffreys_prior
    from repro.core.types import Application, Request
    from repro.models import LM
    from repro.serving import EdgeServer

    traffic = traffic_override or spec["traffic"]
    cfg = config_override or load_config(spec["config_path"])
    gen = load_module(spec["generator"])
    roles = cfg["roles"]
    dims_by_model = {d.name: d for d in roles.values()}
    w_s, per = traffic["window_s"], traffic["per_app_per_window"]
    P, T = traffic["prompt_tokens"], traffic["new_tokens"]
    apps_t = traffic["apps"]
    vocab = min(d.vocab for d in roles.values())
    log(f"cell {workload}: seed {seed}, {seconds} s, trace {int(trace)}; models "
        + ", ".join(f"{r}={d.name}" for r, d in roles.items()))

    # -- set-up: weights
    t = time.perf_counter()
    c0 = ev.compiles
    weights, mcfgs = {}, {}
    for salt, d in enumerate(roles.values()):
        mcfgs[d.name] = arch(d).model_config(d)
        weights[d.name] = make_weights(d, seed, salt)
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), LM(mcfgs[d.name]).abstract_params())
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights[d.name])
        if want != got:
            raise RuntimeError(f"{d.name}: the program's parameter layout changed")
    jax.block_until_ready(weights)
    log(f"setup: weights made on the device in {time.perf_counter() - t:.3f} s "
        f"({ev.compiles - c0} programs compiled)")

    # -- set-up: served-model programs and the latency calibration
    max_rows = per * len(apps_t)
    buckets = [1 << i for i in range((max_rows - 1).bit_length() + 1)]
    backend = StampedBackend({n: (mcfgs[n], 0) for n in weights}, weights, new_tokens=T,
                             seq_multiple=P, batch_hint=buckets[-1])
    t, c0, h0 = time.perf_counter(), ev.compiles, ev.hits
    for name in weights:
        for b in buckets:
            backend.run_batch(name, np.zeros((b, P), np.int32), list(range(b)))
    t_load = time.perf_counter() - t
    t = time.perf_counter()
    for name in weights:
        for b in buckets:
            backend.run_batch(name, np.zeros((b, P), np.int32), list(range(b)))
    log(f"setup: LM programs for batches {buckets} loaded/compiled in {t_load:.3f} s "
        f"({ev.compiles - c0} compiled, {ev.hits - h0} cache hits); calibration "
        f"{time.perf_counter() - t:.3f} s")

    # -- set-up: SneakPeek k-NN models on the benchmark's training sets
    t = time.perf_counter()
    sk = cfg["sneakpeek"]
    train = gen.training_sets(traffic, sk["train_seed"], sk["train_n"])
    sneaks, ref_train = {}, {}
    for app in apps_t:
        x, y = train[app["name"]]
        sp = TimedKNN(x, y, app["num_classes"], k=sk["k"], name=f"{app['name']}-knn",
                      backend="jax", holdout_frac=sk["holdout_frac"], seed=sk["holdout_seed"])
        ref_train[app["name"]] = _holdout_split(x, y, app["num_classes"], sk["holdout_frac"],
                                                sk["holdout_seed"])
        if not np.array_equal(sp.train_x, ref_train[app["name"]][0]):
            raise RuntimeError("KNNSneakPeek's held-out split changed")
        sp.evidence_batch(np.zeros((per, app["feature_dim"]), np.float32))
        sp.calls.clear()
        sp.seconds = 0.0
        sneaks[app["name"]] = sp
    log(f"setup: k-NN fit and kernel warm-up {time.perf_counter() - t:.3f} s")

    apps = {}
    for app in apps_t:
        rec = cfg["recalls"][app["name"]]
        apps[app["name"]] = Application(
            name=app["name"],
            models=[backend.profile(roles["fast"].name, rec["fast"]),
                    backend.profile(roles["accurate"].name, rec["accurate"])],
            penalty=traffic["penalty"], prior=jeffreys_prior(app["num_classes"]),
            expected_freqs=np.asarray(app["stream_freqs"]),
        )
    for m in apps[apps_t[0]["name"]].models:
        log(f"setup: {m.name} l(b) = {m.latency_model[0] * 1e3:.4f} + "
            f"{m.latency_model[1] * 1e3:.4f} * b ms, bytes {m.memory_bytes}")

    # -- the stream
    lead = traffic["lead_in_windows"]
    n_meas = max(1, math.ceil(seconds / w_s - 1e-9))
    stream = gen.windows(traffic, seed, lead + n_meas, vocab)
    prompts = {r["rid"]: r["prompt"] for win in stream for r in win}
    measured = [r for win in stream[lead:] for r in win]
    hbm = int((jax.devices()[0].memory_stats() or {}).get("bytes_limit", 1 << 40))
    server = EdgeServer(apps, make_policy("SneakPeek"), sneakpeeks=sneaks, window_s=w_s,
                        memory_capacity_bytes=hbm, backend=backend,
                        prompt_fn=lambda r: prompts[r.rid])
    tr_first = lead + max(n_meas // 2, n_meas - TRACE_WINDOWS) if trace else -1
    tr_last = lead + n_meas - 1 if trace else -1
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    win_ann, t_trace = None, None
    windows_rec = []
    decided = []  # (now, the window's requests, the program's decisions) per close
    gc.collect()
    gc.freeze()
    gc_pauses, gc_t = [], [0.0]

    def _gc(phase, _info):
        if phase == "start":
            gc_t[0] = time.perf_counter()
        else:
            gc_pauses.append(time.perf_counter() - gc_t[0])

    gc.callbacks.append(_gc)
    c_window = h_window = cold0 = 0
    watch = stalls.Watch().start()
    t0 = time.perf_counter()
    setup_s = t0 + lead * w_s - t_start
    for w, win in enumerate(stream):
        watch.progress = w
        if w == lead:
            c_window, h_window = ev.compiles, ev.hits
            cold0 = server.stats.cold_forwards
        if w == tr_first:
            t_trace = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            win_ann = jax.profiler.TraceAnnotation("bench.window")
            win_ann.__enter__()
            backend.trace = True
            for sp in sneaks.values():
                sp.trace = True
            tracing.enable(True)
        tick = t0 + (w + 1) * w_s
        with span("bench.wait", trace and tr_first <= w <= tr_last):
            delay = tick - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        close = time.perf_counter()
        for r in win:
            server.submit(Request(rid=r["rid"], app=r["app"], arrival_s=r["due_s"],
                                  deadline_s=r["deadline_s"], features=r["features"],
                                  true_label=r["label"]))
        ing0 = sum(sp.seconds for sp in sneaks.values())
        sch0 = server.stats.sched_wall_s
        qw0, q0 = server.stats.queue_wait_s, server.stats.queued
        f0 = len(backend.forwards)
        with span("bench.close", trace and tr_first <= w <= tr_last):
            out = server.run_window(close - t0)
        decided.append((close - t0, win, [] if out is None else [
            (e.request.rid, e.model, e.order, e.batch_id) for e in out["schedule"].sorted_entries()]))
        if w >= lead and not tr_first <= w <= tr_last:
            fw = backend.forwards[f0:]
            windows_rec.append({
                "w": w, "late_s": close - tick, "close_s": time.perf_counter() - close,
                "ingest_s": sum(sp.seconds for sp in sneaks.values()) - ing0,
                "sched_s": server.stats.sched_wall_s - sch0,
                "exec_s": sum(f["prefill_s"] + f["decode_s"] for f in fw), "forwards": len(fw),
                "queue_wait_s": server.stats.queue_wait_s - qw0,
                "queued": server.stats.queued - q0,
            })
        if w == tr_last:
            win_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing.enable(False)
            backend.trace = False
            for sp in sneaks.values():
                sp.trace = False
    t_end = time.perf_counter()
    watch.stop()
    gc.callbacks.remove(_gc)
    gc.unfreeze()
    in_window = (ev.compiles - c_window, ev.hits - h_window)
    log(f"window: {len(measured)} requests due over {n_meas * w_s:.3f} s; stream ran "
        f"{t_end - t0:.3f} s; mean close lateness "
        f"{1e3 * np.mean([x['late_s'] for x in windows_rec]):.4f} ms, max "
        f"{1e3 * np.max([x['late_s'] for x in windows_rec]):.4f} ms; programs compiled "
        f"in the window {in_window[0]}, loaded from cache {in_window[1]}")

    log(f"window: {len(gc_pauses)} garbage collections, longest "
        f"{1e3 * max(gc_pauses, default=0.0):.3f} ms, total {1e3 * sum(gc_pauses):.3f} ms")
    for x in sorted(windows_rec, key=lambda x: -x["close_s"])[:5]:
        log(f"slow close: window {x['w']} started {1e3 * x['late_s']:.3f} ms late, took "
            f"{1e3 * x['close_s']:.3f} ms (ingest {1e3 * x['ingest_s']:.3f}, scheduling "
            f"{1e3 * (x['sched_s'] - x['ingest_s']):.3f}, {x['forwards']} forwards "
            f"{1e3 * x['exec_s']:.3f} ms)")
    held = watch.stalls()
    log(f"window: {len(held)} host stalls; over the stream " + ", ".join(
        f"{k} +{v}" for k, v in watch.totals().items()))
    for x in held[:8]:
        log(f"stall: {x['kind']} {1e3 * x['s']:.1f} ms at {1e3 * (x['t'] - t0):.1f} ms "
            f"(window {int((x['t'] - t0) / w_s)}) in {x['where']}; "
            + ", ".join(f"{k} +{v}" for k, v in x["delta"].items()))

    # -- end-to-end metrics, from the wall clock only
    done, served_by = backend.done, backend.served_by
    lat = np.array([done[r["rid"]] - (t0 + r["due_s"]) if r["rid"] in done else np.inf
                    for r in measured])
    dl = np.array([r["deadline_s"] - r["due_s"] for r in measured])
    role_of = {d.name: role for role, d in roles.items()}
    recall = np.array([
        cfg["recalls"][r["app"]][role_of[served_by[r["rid"]]]][r["label"]]
        if r["rid"] in done else 0.0 for r in measured])
    failed = int(np.sum(~np.isfinite(lat)))
    fin = lat[np.isfinite(lat)]
    e2e = {
        "attain": 100.0 * float(np.mean(lat <= dl)),
        "utility": float(np.mean(realized_utility(recall, dl, lat, traffic["penalty"]))),
        "p50_ms": 1e3 * float(np.percentile(fin, 50)) if len(fin) else float("inf"),
        "p95_ms": 1e3 * float(np.percentile(fin, 95)) if len(fin) else float("inf"),
        "setup_s": setup_s,
    }
    mem_peak = _peak_bytes(jax.devices()[:spec["cell"]["chips"]])

    # -- per-layer record (window forwards and assignments)
    meas_rids = {r["rid"] for r in measured}
    t_first = t0 + lead * w_s
    t_last = t_trace if trace else float("inf")
    rec = {
        "windows": windows_rec,
        "forwards": [f for f in backend.forwards if t_first <= f["t"] < t_last],
        "requests": [{"model": served_by[r]} for r in sorted(meas_rids) if r in done],
        "roles": roles, "dims_by_model": dims_by_model,
        "prompt_len": P, "new_tokens": T, "peaks": None, "trace": None, "spans": None,
        "cold_forwards": server.stats.cold_forwards - cold0,
    }

    # -- free the program's state, then the checks
    tokens = {rid: backend.tokens[rid] for rid in meas_rids if rid in done}
    variants = {a: [scheduler_ref.Variant(m.name, m.recalls, m.latency_s, m.load_latency_s,
                                          int(backend.model_bytes(m.name)), m.latency_model)
                    for m in app.models] for a, app in apps.items()}
    server.close()
    backend.free()
    del server
    gc.collect()
    t = time.perf_counter()
    checks = {}
    rows = wrong = ties = 0
    for app in apps_t:
        x, y = ref_train[app["name"]]
        r_, w_, t_ = check.knn_wrong_rows(sneaks[app["name"]].calls, x, y, sk["k"],
                                          app["num_classes"])
        rows, wrong, ties = rows + r_, wrong + w_, ties + t_
    checks["knn_rows_wrong"] = {"value": wrong, "limit": 0}
    n_dec, n_diff = replay_decisions(decided, sneaks, variants, traffic["penalty"], hbm)
    checks["decisions_differing"] = {"value": n_diff, "limit": 0}
    by_model = {}
    for rid in tokens:
        by_model.setdefault(served_by[rid], []).append(rid)
    sample = check.sample_rids(by_model, seed)
    gaps, ctl = {}, {}
    for name, rids in sample.items():
        if not rids:
            continue
        d = dims_by_model[name]
        pr = np.stack([prompts[r] for r in rids])
        sv = np.stack([tokens[r] for r in rids])
        key = f"gap_{role_of[name]}"
        gaps[name] = check.served_gap(d, weights[name], pr, sv, check.SAMPLE_PER_MODEL)
        checks[key] = {"value": gaps[name], "limit": arch(d).GAP_LIMIT}
        if control:
            ctl[key] = check.served_gap(d, weights[name], pr, sv, check.SAMPLE_PER_MODEL,
                                        quant=True)
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and bool(gaps)
    log(f"checks: {rows} k-NN rows ({ties} ties), {len(decided)} windows' {n_dec} "
        f"decisions replayed ({n_diff} differ), sample "
        + ", ".join(f"{n}: {len(r)} requests" for n, r in sample.items())
        + f"; reference {time.perf_counter() - t:.3f} s")

    result = {"correct": correct, "attempted": len(measured), "failed": failed}
    if trace:
        from bench import trace as trace_mod
        from bench.peaks import peaks_for

        red = trace_mod.reduce(trace_mod.load(trace_dir))
        rec["spans"] = spans.reduce(spans.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(spans.idle_line(rec["spans"]))
        rec["trace"], rec["peaks"] = red, peaks_for(dev["kind"])
        for name, d in dims_by_model.items():
            b = max((f["padded"] for f in rec["forwards"] if f["model"] == name), default=0)
            if b:
                _, bound = flops.least_time(*flops.decode_step_cost(d, b, P + 1), rec["peaks"])
                log(f"trace: decode steps of {name} are {bound}-bound at batch {b}")
        metrics = {}
        for m in spec["per_layer"]:
            v = load_module(spec["metric_dir"] / f"{m['name']}.py").read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        dev_out = dict(dev, memory_peak_bytes=mem_peak, busy_s=red["busy_s"],
                       window_s=red["window_s"])
        result["device"] = dev_out
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in spec["end_to_end"]}
        result["device"] = dict(dev, memory_peak_bytes=mem_peak)
    if control:
        ctl_checks = dict(checks, **{k: {"value": v, "limit": checks[k]["limit"]}
                                     for k, v in ctl.items()})
        result["control"] = {"correct": all(c["value"] <= c["limit"]
                                            for c in ctl_checks.values()),
                             "checks": ctl_checks}
    result["stream"] = {"last_close_late_ms": 1e3 * windows_rec[-1]["late_s"],
                        "max_close_late_ms": 1e3 * max(x["late_s"] for x in windows_rec)}
    result["checks"] = checks
    log("e2e: " + ", ".join(f"{k}={v}" for k, v in e2e.items()) + f"; failed {failed}")
    return result


def main(args, t_start: float, root: Path = ROOT) -> int:
    """CLI body: one run, the checks on the last lines of standard error and
    the result as the last line of standard output."""
    if not (root / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not beside the benchmark", file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace), root=root,
                  cache_dir=root / ".jax_cache", t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(f"correct: {res['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
