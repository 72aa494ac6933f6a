"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on the CPU) vs pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.knn.ops import knn_class_votes, knn_topk
from repro.kernels.ssd.ops import ssd
from repro.kernels.utility.ops import utility_scores
from repro.models.attention import flash_attention as model_flash


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------- flash


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,window",
    [
        (2, 128, 4, 4, 32, 0),     # MHA
        (1, 256, 8, 2, 64, 0),     # GQA
        (2, 96, 4, 1, 32, 0),      # MQA, padded seq
        (1, 256, 4, 2, 32, 64),    # sliding window
        (1, 130, 2, 2, 16, 32),    # window + padding
    ],
)
def test_flash_attention_sweep(b, s, hq, hkv, d, window, dtype):
    rng = np.random.default_rng(hash((b, s, hq, hkv, d, window)) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    out_k = flash_attention(q, k, v, window=window)
    out_r = model_flash(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True, window=window, q_chunk=max(s // 4, 16), kv_chunk=max(s // 4, 16),
    )
    np.testing.assert_allclose(
        np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


def test_flash_attention_causality():
    """Future keys must not influence output: perturb k/v after position t."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    out1 = flash_attention(q, k, v)
    k2 = k.at[:, 40:].set(999.0)
    v2 = v.at[:, 40:].set(-999.0)
    out2 = flash_attention(q, k2, v2)
    np.testing.assert_allclose(out1[:, :40], out2[:, :40], atol=1e-6)


# ---------------------------------------------------------------- decode


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,hkv,g,s,d,window,block_k",
    [
        (2, 2, 4, 256, 32, 0, 64),
        (3, 1, 8, 300, 64, 0, 128),   # MQA, padded
        (2, 4, 1, 128, 32, 0, 32),    # MHA
        (2, 2, 2, 256, 32, 64, 64),   # ring/window masking
    ],
)
def test_decode_attention_sweep(b, hkv, g, s, d, window, block_k, dtype):
    rng = np.random.default_rng(hash((b, hkv, g, s, d, window)) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, hkv, g, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    lengths = jnp.asarray(rng.integers(max(window, 1), s + 1, size=b), jnp.int32)
    o_k = decode_attention_pallas(q, k, v, lengths, window=window, block_k=block_k,
                                  interpret=True)
    o_r = decode_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        lengths, window=window,
    )
    np.testing.assert_allclose(
        np.asarray(o_k, np.float32), np.asarray(o_r, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


def test_decode_respects_length_mask():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 64, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1, 64, 16)), jnp.float32)
    o1 = decode_attention_pallas(q, k, v, jnp.asarray([32]), block_k=16, interpret=True)
    k2 = k.at[:, :, 32:].set(555.0)
    v2 = v.at[:, :, 32:].set(-555.0)
    o2 = decode_attention_pallas(q, k2, v2, jnp.asarray([32]), block_k=16, interpret=True)
    np.testing.assert_allclose(o1, o2, atol=1e-6)


# ---------------------------------------------------------------- knn


@pytest.mark.parametrize(
    "q,n,d,k,nc",
    [(16, 256, 8, 5, 3), (37, 700, 16, 1, 4), (128, 512, 32, 8, 6), (5, 40, 4, 5, 2)],
)
def test_knn_sweep(q, n, d, k, nc):
    rng = np.random.default_rng(hash((q, n, d, k)) % 2**31)
    queries = rng.normal(size=(q, d)).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, nc, n).astype(np.int32)
    dk, _ = knn_topk(queries, x, y, k, use_kernel=True)
    dr, _ = knn_topk(queries, x, y, k, use_kernel=False)
    np.testing.assert_allclose(np.sort(np.asarray(dk), 1), np.sort(np.asarray(dr), 1), atol=1e-3)
    vk = knn_class_votes(queries, x, y, k, nc, use_kernel=True)
    vr = knn_class_votes(queries, x, y, k, nc, use_kernel=False)
    # vote counts may differ only at exact distance ties; allow none here
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))
    assert np.all(np.asarray(vk).sum(1) == k)


def test_knn_votes_match_bruteforce_numpy():
    rng = np.random.default_rng(7)
    queries = rng.normal(size=(10, 6)).astype(np.float32)
    x = rng.normal(size=(100, 6)).astype(np.float32)
    y = rng.integers(0, 3, 100).astype(np.int32)
    votes = np.asarray(knn_class_votes(queries, x, y, 5, 3, use_kernel=True))
    d2 = ((queries[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    for i in range(10):
        nn = np.argsort(d2[i])[:5]
        expected = np.bincount(y[nn], minlength=3)
        np.testing.assert_array_equal(votes[i], expected)


# ---------------------------------------------------------------- utility


@pytest.mark.parametrize("penalty", ["step", "linear", "sigmoid", "none"])
@pytest.mark.parametrize("r,m", [(7, 3), (64, 5), (300, 8)])
def test_utility_kernel_sweep(penalty, r, m):
    """Pallas Eq. 2 scoring vs jnp oracle vs the numpy fast-path math."""
    from repro.core.utility import PENALTIES

    # Deterministic seed (str hash() is salted per process).
    rng = np.random.default_rng([r, m, len(penalty)])
    acc = rng.uniform(0, 1, (r, m))
    deadlines = rng.uniform(-0.05, 0.3, r)  # includes past/zero deadlines
    completions = rng.uniform(0.0, 0.6, (r, m))
    uk, mk = utility_scores(acc, deadlines, completions, penalty=penalty, use_kernel=True)
    ur, mr = utility_scores(acc, deadlines, completions, penalty=penalty, use_kernel=False)
    g = PENALTIES[penalty](deadlines[:, None], completions)
    u_np = acc * (1.0 - np.clip(g, 0.0, 1.0))
    np.testing.assert_allclose(np.asarray(uk), u_np, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ur), u_np, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(mk), u_np.mean(axis=0), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(mr), u_np.mean(axis=0), atol=1e-5, rtol=1e-5)


def test_utility_kernel_broadcast_completions():
    """(M,) completions (one per variant, shared across the group) broadcast."""
    rng = np.random.default_rng(4)
    acc = rng.uniform(0, 1, (33, 4))
    deadlines = rng.uniform(0.01, 0.3, 33)
    comp = rng.uniform(0.0, 0.4, 4)
    uk, mk = utility_scores(acc, deadlines, comp, penalty="sigmoid", use_kernel=True)
    ur, _ = utility_scores(acc, deadlines, np.broadcast_to(comp, acc.shape),
                           penalty="sigmoid", use_kernel=False)
    np.testing.assert_allclose(np.asarray(uk), np.asarray(ur), atol=1e-6)
    assert np.asarray(mk).shape == (4,)


# ---------------------------------------------------------------- ssd


@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [(2, 64, 4, 8, 16, 16), (1, 128, 2, 16, 8, 32), (2, 48, 8, 8, 32, 16)],
)
def test_ssd_kernel_sweep(b, s, h, p, n, chunk):
    rng = np.random.default_rng(hash((b, s, h, p, n)) % 2**31)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, h))) * 0.5 + 0.1, jnp.float32)
    a_log = jnp.asarray(rng.normal(size=(h,)) * 0.3, jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, n)) * 0.3, jnp.float32)
    yk, sk = ssd(x, dt, a_log, bm, cm, chunk=chunk, use_kernel=True)
    yr, sr = ssd(x, dt, a_log, bm, cm, chunk=chunk, use_kernel=False)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), atol=2e-4, rtol=1e-3)


def test_ssd_state_continuity():
    """Final state after S steps equals running the recurrence stepwise."""
    rng = np.random.default_rng(9)
    b, s, h, p, n = 1, 32, 2, 4, 8
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(b, s, h))) * 0.3 + 0.1, jnp.float32)
    a_log = jnp.zeros((h,), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, n)) * 0.3, jnp.float32)
    _, s_full = ssd(x, dt, a_log, bm, cm, chunk=8, use_kernel=True)
    # two halves, threading state through the sequential reference
    from repro.kernels.ssd.ref import ssd_ref

    a = -jnp.exp(a_log)
    dA = dt * a[None, None, :]
    xdt = x * dt[..., None]
    _, s1 = ssd_ref(xdt[:, :16], dA[:, :16], bm[:, :16], cm[:, :16])
    state = s1
    for t in range(16, 32):
        decay = jnp.exp(dA[:, t, :])
        upd = jnp.einsum("bn,bhp->bhpn", bm[:, t], xdt[:, t])
        state = decay[:, :, None, None] * state + upd
    np.testing.assert_allclose(np.asarray(s_full), np.asarray(state), atol=1e-4)
