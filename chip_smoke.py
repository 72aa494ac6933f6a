#!/usr/bin/env python3
"""Smoke run of the SneakPeek serving path on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a 2x2 host: the sharded scheduler only

One chip runs three phases through the entry points a user calls:

* ingest  -- the paper's three applications (``APP_SPECS``) with k-NN
  SneakPeek models on the Pallas kernel, compiled for the chip; the votes
  are compared with ``kernels/knn/ref.py`` on the same inputs (rows whose
  k-th and (k+1)-th reference distances tie within f32 rounding are
  counted apart, any other difference fails).
* serve   -- three 100 ms windows of the paper's 12 requests (4 per app,
  ~150 ms deadlines) through ``EdgeServer`` with the SneakPeek policy and
  a ``CompiledBackend`` serving tinyllama-1.1b and mamba2-130m at their
  published widths (random weights from a seed), once on the default
  route (numpy fast path) and once with ``pipeline=True`` (compiled window
  programs on the chip).  The served LMs are checked against their own
  full-sequence forward.  Compilation is warmed up first and reported
  apart from the served time.
* parity  -- the decisions of both serving runs and of the scalar
  reference (``make_policy(..., fastpath=False)``) must be identical.

``--four-chips`` schedules the same windows with ``shard=4`` and with the
unsharded pipeline and requires identical decisions over a mesh of four
distinct devices; nothing else runs.

The last line of standard output is one JSON object naming the device.  The
script exits non-zero, without that line, when JAX finds no TPU, when the
repository's ``src/`` is not beside it, or when any phase fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

WINDOWS = 3
PER_APP = 4
WINDOW_S = 0.1
DEADLINE_S = 0.15
PROMPT_LEN = 8
NEW_TOKENS = 4
# Padded batch sizes a window can reach: CompiledBackend rounds a batch up
# to a power of two, and fuses consecutive same-model batches (<= 12 rows).
BATCH_BUCKETS = (1, 2, 4, 8, 16)
# Each app's fastest paper variant is served by the small LM, its most
# accurate one by the large LM, so the scheduler picks between the two.
SERVED = ("mamba2-130m", "tinyllama-1.1b")
HBM_BYTES = 16 * 2**30  # one TPU v5e chip
# Relative L2 gap allowed between a served LM's cached prefill/decode logits
# and its full-sequence forward: bf16 weights and activations.
LOGIT_RTOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def paper_windows(seed: int):
    """WINDOWS consecutive windows of the paper's traffic (fresh objects on
    every call: ingest attaches evidence to requests in place)."""
    from repro.data.applications import APP_SPECS, make_requests

    reqs = []
    for w in range(WINDOWS):
        batch = make_requests(
            list(APP_SPECS.values()), per_app=PER_APP, window_s=WINDOW_S,
            mean_deadline_s=DEADLINE_S, seed=seed + w, start_rid=len(reqs),
        )
        for r in batch:
            r.arrival_s += w * WINDOW_S
            r.deadline_s += w * WINDOW_S
        reqs.extend(batch)
    return reqs


def decisions(outs) -> list[list[tuple]]:
    """Per window: (rid, worker, model, order, batch) of every entry."""
    return [
        [(e.request.rid, e.worker, e.model, e.order, e.batch_id)
         for e in out["schedule"].sorted_entries()]
        for out in outs
    ]


def count_diffs(a: list[list[tuple]], b: list[list[tuple]]):
    """(entries that differ, first differing window or None)."""
    n, first = abs(len(a) - len(b)), None
    for w, (wa, wb) in enumerate(zip(a, b)):
        d = sum(x != y for x, y in zip(wa, wb)) + abs(len(wa) - len(wb))
        if d and first is None:
            first = w
        n += d
    if n and first is None:
        first = min(len(a), len(b))
    return n, first


# --------------------------------------------------------------- ingest


def knn_tie_tol(queries: np.ndarray, train_x: np.ndarray) -> np.ndarray:
    """Per-query f32 rounding bound of the kernel's distance
    |x|^2 - 2 q.x: D ulps of its largest term, on both sides compared."""
    eps = np.finfo(np.float32).eps
    xmax = float(np.sqrt((train_x.astype(np.float64) ** 2).sum(1).max()))
    qn = np.sqrt((queries.astype(np.float64) ** 2).sum(1))
    return 2 * queries.shape[1] * eps * (xmax**2 + 2 * qn * xmax)


def ingest_phase(sneakpeeks, seed: int) -> dict:
    """k-NN votes from the kernel against the jnp reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.knn import ops
    from repro.kernels.knn.ref import knn_class_votes_ref, knn_ref

    reqs = paper_windows(seed)
    rows = differ = ties = 0
    lowered_as_kernel = True
    for name, sp in sneakpeeks.items():
        queries = [
            np.stack([r.features for r in reqs[w * 12:(w + 1) * 12] if r.app == name])
            for w in range(WINDOWS)
        ] + [sp._hold_x]  # the held-out set measured_recalls scores
        for q in queries:
            votes = sp.evidence_batch(q)
            hlo = ops.knn_class_votes.lower(
                q, sp.train_x, sp.train_y, sp.k, sp.num_classes).as_text()
            lowered_as_kernel &= "tpu_custom_call" in hlo
            with jax.default_matmul_precision("highest"):
                ref = np.asarray(knn_class_votes_ref(
                    jnp.asarray(q), jnp.asarray(sp.train_x), jnp.asarray(sp.train_y),
                    sp.k, sp.num_classes))
                d_ref = np.asarray(knn_ref(
                    jnp.asarray(q), jnp.asarray(sp.train_x), jnp.asarray(sp.train_y),
                    sp.k + 1)[0])
            bad = np.any(votes != ref, axis=1)
            gap = d_ref[:, sp.k] - d_ref[:, sp.k - 1]
            tie = bad & (gap <= knn_tie_tol(q, sp.train_x))
            rows += len(q)
            differ += int(bad.sum())
            ties += int(tie.sum())
    return {"rows": rows, "differ": differ, "ties": ties,
            "failures": differ - ties, "lowered_as_kernel": lowered_as_kernel}


# ---------------------------------------------------------------- serve


def make_backend(archs):
    from repro.serving import CompiledBackend

    return CompiledBackend(
        {name: (archs[name], i) for i, name in enumerate(SERVED)},
        new_tokens=NEW_TOKENS, seq_multiple=PROMPT_LEN, batch_hint=max(BATCH_BUCKETS),
    )


def serving_apps(backend):
    """The paper's applications with their two candidates mapped onto the
    served LMs: recalls from the app's fastest / most accurate variant,
    latency, swap cost and footprint from the backend's own estimates."""
    from repro.data.applications import APP_SPECS, make_application

    apps = {}
    for name, spec in APP_SPECS.items():
        base = make_application(spec, seed=0)
        fast, accurate = base.models[0], base.models[-1]
        models = [backend.profile(SERVED[0], fast.recalls),
                  backend.profile(SERVED[1], accurate.recalls)]
        apps[name] = dataclasses.replace(base, models=models)
    return apps


def prompt_ids(rid: int, vocab: int) -> np.ndarray:
    """Prompt token ids seeded per request."""
    return np.random.default_rng(1000 + rid).integers(0, vocab, PROMPT_LEN).astype(np.int32)


def check_lm(backend, name: str, vocab: int) -> float:
    """Relative L2 gap between the cached prefill + one decode step and the
    full-sequence forward of the same tokens; finite and shaped logits."""
    import jax
    import jax.numpy as jnp

    model, params = backend._get(name)
    prompts = jnp.asarray(np.stack([prompt_ids(rid, vocab) for rid in range(4)]))
    prefill = jax.jit(model.prefill, static_argnames="max_len")
    logits, cache = prefill(params, prompts, max_len=PROMPT_LEN + backend.new_tokens)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    step, _ = jax.jit(model.decode_step)(params, cache, tok[:, None])
    full, _ = jax.jit(model.forward)(params, jnp.concatenate([prompts, tok[:, None]], 1))
    got = np.stack([np.asarray(logits, np.float32), np.asarray(step, np.float32)], 1)
    want = np.asarray(full[:, -2:], np.float32)
    out_vocab = backend.variants[name][0].vocab_size
    if got.shape != (4, 2, out_vocab) or not np.isfinite(got).all():
        raise RuntimeError(f"{name}: logits {got.shape} finite={np.isfinite(got).all()}")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def serve(apps, sneakpeeks, backend, seed: int, **route):
    """One EdgeServer run over the paper windows; backend=None schedules
    without executing (decisions only)."""
    from repro.core import make_policy
    from repro.serving import EdgeServer

    policy = route.pop("policy", None) or make_policy("SneakPeek")
    extra = {}
    if backend is not None:
        vocab = min(cfg.vocab_size for cfg, _ in backend.variants.values())
        extra = {"backend": backend, "prompt_fn": lambda r: prompt_ids(r.rid, vocab)}
    with EdgeServer(apps, policy, sneakpeeks=sneakpeeks, window_s=WINDOW_S,
                    memory_capacity_bytes=HBM_BYTES, **extra, **route) as srv:
        t0 = time.perf_counter()
        outs, stats = srv.run(paper_windows(seed))
        wall = time.perf_counter() - t0
    return outs, stats, wall


def print_windows(label: str, outs) -> None:
    for w, out in enumerate(outs):
        reps = out["reports"] or []
        batches = " ".join(
            f"{r.model}x{r.batch_size}({r.prefill_s * 1e3:.3f}+{r.decode_s * 1e3:.3f}ms)"
            for r in reps)
        log(f"[serve:{label}] window {w} requests={len(out['schedule'].entries)} "
            f"swaps={sum(r.swap_s > 0 for r in reps)} "
            f"mean_utility={float(np.mean(out['eval'].utilities)):.6f} batches: {batches}")


def serve_phase(sneakpeeks, archs, seed: int) -> dict:
    """Warm-up, both served routes, the scalar reference, and the checks."""
    from repro.core import make_policy

    backend = make_backend(archs)
    t0 = time.perf_counter()
    for name in SERVED:
        for b in BATCH_BUCKETS:
            backend.run_batch(name, np.zeros((b, PROMPT_LEN), np.int32), list(range(b)))
    lm_compile_s = time.perf_counter() - t0
    apps = serving_apps(backend)
    for name in SERVED:
        m = apps["fall_detection"].models[SERVED.index(name)]
        log(f"[serve] {name}: l(m,b)={m.latency_model[0] * 1e3:.3f}+"
            f"{m.latency_model[1] * 1e3:.3f}*b ms  swap={m.load_latency_s * 1e3:.3f} ms  "
            f"bytes={m.memory_bytes}")
    vocab = min(archs[name].vocab_size for name in SERVED)
    gaps = {name: check_lm(backend, name, vocab) for name in SERVED}
    log(f"[serve] cached-vs-forward logit rel gap: "
        + " ".join(f"{k}={v:.3e}" for k, v in gaps.items()))
    t0 = time.perf_counter()
    serve(apps, sneakpeeks, None, seed, pipeline=True)  # compiles the window programs
    sched_compile_s = time.perf_counter() - t0
    log(f"[serve] cold compile: LM shapes {lm_compile_s:.3f} s, "
        f"window programs {sched_compile_s:.3f} s")

    runs = {}
    for label, route in (("default", {}), ("pipeline", {"pipeline": True})):
        outs, stats, wall = serve(apps, sneakpeeks, backend, seed, **route)
        print_windows(label, outs)
        served = {rid for o in outs for r in o["reports"] or [] for rid in r.request_ids}
        log(f"[serve:{label}] served {len(served)}/{stats.requests} requests in "
            f"{wall:.6f} s (scheduling {stats.sched_wall_s:.6f} s, execution "
            f"{stats.exec_wall_s:.6f} s), violations {stats.violations}, "
            f"mean utility {stats.mean_utility:.6f}")
        runs[label] = (decisions(outs), len(served), stats.requests)
    ref_outs, _, _ = serve(apps, sneakpeeks, None, seed,
                           policy=make_policy("SneakPeek", fastpath=False))
    ref = decisions(ref_outs)
    parity = {label: count_diffs(d, ref) for label, (d, _, _) in runs.items()}
    for label, (n, first) in parity.items():
        where = "" if first is None else f" (first in window {first})"
        log(f"[parity] {label} vs scalar reference: {n} differing decisions{where}")
    n_req = WINDOWS * PER_APP * 3
    return {
        "lm_gaps": gaps,
        "served_all": all(s == n_req and r == n_req for _, s, r in runs.values()),
        "parity_diffs": sum(n for n, _ in parity.values()),
        "compile_s": lm_compile_s + sched_compile_s,
    }


# ------------------------------------------------------------ four chips


def four_chip_phase(seed: int) -> bool:
    """shard=4 against the unsharded pipeline on the same windows."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from benchmarks.sched_bench import shard_child
    from repro.core.shard import shard_mesh
    from repro.data.applications import build_benchmark_suite

    mesh = shard_mesh(4)
    ids = sorted(d.id for d in mesh.devices.flat)
    probe = jax.device_put(np.arange(8.0), NamedSharding(mesh, PartitionSpec("shard")))
    held = sorted(s.device.id for s in probe.addressable_shards)
    log(f"[four] mesh devices {ids}; a row-sharded probe is held by {held}")
    ok = len(set(ids)) == 4 and held == ids

    apps, sneaks = build_benchmark_suite(backend="jax", seed=seed)
    runs = {}
    for label, route in (("pipeline", {"pipeline": True}), ("shard=4", {"shard": 4})):
        serve(apps, sneaks, None, seed, **route)  # compiles
        outs, _, wall = serve(apps, sneaks, None, seed, **route)
        runs[label] = decisions(outs)
        log(f"[four] {label}: {sum(map(len, runs[label]))} decisions, e2e {wall:.6f} s")
    n, first = count_diffs(runs["shard=4"], runs["pipeline"])
    log(f"[four] paper windows: {n} differing decisions"
        + ("" if first is None else f" (first in window {first})"))
    ok &= n == 0

    row = shard_child(4, 4096, 0)  # asserts decision parity on a 4096-request window
    log(f"[four] {row['requests']}-request LO-EDF window: tile phase "
        f"{row['tile_full_s']:.6e} s on {row['tile_rows_full']} rows vs "
        f"{row['tile_shard_s']:.6e} s on one shard's {row['tile_rows_shard']}; e2e "
        f"unsharded {row['e2e_base_s']:.6e} s vs shard=4 {row['e2e_shard_s']:.6e} s; "
        f"parity {row['parity']}; stats {row['shard_stats']}")
    return ok and row["parity"] and row["shard_stats"]["num_shards"] == 4


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard=4 scheduler against the unsharded pipeline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    log(f"[device] {device}")
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if args.four_chips and device["count"] < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {device['count']}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(ROOT)
    hits = {"/jax/compilation_cache/cache_hits": 0, "/jax/compilation_cache/cache_misses": 0}

    def count(event, **_):
        if event in hits:
            hits[event] += 1

    jax.monitoring.register_event_listener(count)

    if args.four_chips:
        ok = four_chip_phase(args.seed)
    else:
        from repro.configs import ARCHS
        from repro.data.applications import APP_SPECS, make_sneakpeek

        sneaks = {name: make_sneakpeek(spec, seed=args.seed, backend="jax")
                  for name, spec in APP_SPECS.items()}
        ing = ingest_phase(sneaks, args.seed)
        log(f"[ingest] {ing['rows']} query rows, {ing['differ']} differ from the "
            f"reference, of which {ing['ties']} are k-th/(k+1)-th distance ties; "
            f"kernel lowered as tpu_custom_call: {ing['lowered_as_kernel']}")
        srv = serve_phase(sneaks, ARCHS, args.seed)
        ok = (ing["failures"] == 0 and ing["lowered_as_kernel"]
              and srv["served_all"] and srv["parity_diffs"] == 0
              and all(g <= LOGIT_RTOL for g in srv["lm_gaps"].values()))
    log(f"[cache] {cache_dir}: hits {hits['/jax/compilation_cache/cache_hits']}, "
        f"misses {hits['/jax/compilation_cache/cache_misses']}")
    if not ok:
        print("chip_smoke: a phase failed (see above)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
