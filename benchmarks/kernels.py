"""Kernel parity + structural benchmark (per-kernel FLOP/byte accounting).

Wall-clock on this CPU container is meaningless for TPU kernels; what is
recorded instead: parity vs the jnp oracle (max abs err) and the
analytic FLOPs / HBM bytes per call at representative serving shapes —
the numbers the §Roofline analysis uses for the kernels' hot paths.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmarks.common import print_table, save_result


def bench_kernels(quick=False):
    import jax.numpy as jnp

    from repro.kernels import for_platform
    from repro.kernels.decode_attention.kernel import decode_attention_pallas
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.knn.ops import knn_topk
    from repro.kernels.ssd.ops import ssd
    from repro.models.attention import flash_attention as model_flash

    rng = np.random.default_rng(0)
    rows = []

    # flash attention @ small proxy of prefill shape
    b, s, hq, hkv, d = (1, 256, 4, 2, 64) if quick else (2, 384, 8, 2, 64)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    out_k = flash_attention(q, k, v)
    out_r = model_flash(q, k, v, causal=True, q_chunk=128, kv_chunk=128)
    flops = 4 * (s * s / 2) * hq * d * b  # causal QK^T + PV
    rows.append({
        "kernel": "flash_attention", "max_err": float(jnp.abs(out_k - out_r).max()),
        "gflops_per_call": flops / 1e9,
        "hbm_mb": (q.size + k.size + v.size + out_k.size) * 4 / 2**20,
    })

    # decode attention @ cache-streaming shape
    b, hkv, g, s, d = (2, 2, 4, 1024, 64) if quick else (2, 4, 8, 2048, 128)
    q2 = jnp.asarray(rng.normal(size=(b, hkv, g, d)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, hkv, s, d)), jnp.float32)
    lengths = jnp.full((b,), s, jnp.int32)
    o_k = for_platform(
        functools.partial(decode_attention_pallas, block_k=256), q2, kc, vc, lengths)
    o_r = decode_attention_ref(q2, kc, vc, lengths)
    rows.append({
        "kernel": "decode_attention", "max_err": float(jnp.abs(o_k - o_r).max()),
        "gflops_per_call": 4 * s * hkv * g * d * b / 1e9,
        "hbm_mb": (kc.size + vc.size) * 4 / 2**20,  # cache streaming dominates
    })

    # knn (SneakPeek evidence)
    qn, n, dim, kk = (64, 1024, 16, 5) if quick else (128, 2048, 32, 5)
    queries = rng.normal(size=(qn, dim)).astype(np.float32)
    xs = rng.normal(size=(n, dim)).astype(np.float32)
    ys = rng.integers(0, 6, n).astype(np.int32)
    dk, _ = knn_topk(queries, xs, ys, kk, use_kernel=True)
    dr, _ = knn_topk(queries, xs, ys, kk, use_kernel=False)
    rows.append({
        "kernel": "knn", "max_err": float(np.abs(np.sort(dk, 1) - np.sort(dr, 1)).max()),
        "gflops_per_call": 2 * qn * n * dim / 1e9,
        "hbm_mb": (queries.size + xs.size) * 4 / 2**20,
    })

    # ssd chunk kernel
    b, s, h, p, nst, chunk = (1, 128, 4, 16, 16, 32) if quick else (1, 256, 8, 32, 64, 64)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, h))) * 0.4 + 0.1, jnp.float32)
    a_log = jnp.asarray(rng.normal(size=(h,)) * 0.2, jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, nst)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, nst)) * 0.3, jnp.float32)
    yk, sk = ssd(x, dt, a_log, bm, cm, chunk=chunk, use_kernel=True)
    yr, sr = ssd(x, dt, a_log, bm, cm, chunk=chunk, use_kernel=False)
    rows.append({
        "kernel": "ssd", "max_err": float(max(jnp.abs(yk - yr).max(), jnp.abs(sk - sr).max())),
        "gflops_per_call": (2 * s * chunk * h * p + 6 * s * h * p * nst) * b / 1e9,
        "hbm_mb": (x.size * 2 + bm.size * 2) * 4 / 2**20,
    })

    print_table("Kernels — parity vs jnp oracle + per-call cost",
                rows, ["kernel", "max_err", "gflops_per_call", "hbm_mb"])
    save_result("kernels", {r["kernel"]: r for r in rows})
    return rows
