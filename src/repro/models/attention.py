"""Attention: GQA/MQA, causal global + banded sliding-window, prefill + decode.

The full-sequence path is written flash-style in pure jnp (lax.scan over
KV chunks with online softmax) so that:

  * 32k x 32k score matrices are never materialized (prefill memory),
  * it doubles as the numerical oracle for the Pallas kernels
    (``repro.kernels.flash_attention.ref`` re-exports it),
  * local (sliding-window) attention does true banded work — FLOPs scale
    with S*window, not S^2 (static band offsets + traced dynamic_slice).

Decode is a single-token einsum over the KV cache with a position mask;
with the cache sequence-sharded over the ``model`` mesh axis the SPMD
partitioner emits the split-KV (flash-decoding) max/sum all-reduces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.layers import rmsnorm, rope
from repro.models.spec import P

__all__ = [
    "attn_spec",
    "flash_attention",
    "decode_attention",
    "attn_forward",
    "attn_decode",
    "attention_options",
]

_NEG = -0.7 * float(jnp.finfo(jnp.float32).max)

# ---------------------------------------------------------------- options
# Compile-strategy switches (threaded via context, not config, so the
# dry-run cost model and the §Perf hillclimb can flip them without
# touching model code):
#   unroll: replace the lax.scan/map block loops with static python loops
#     (bigger HLO, but XLA cost_analysis counts every block — required for
#     honest roofline FLOPs, since while-bodies are counted once).
#   skip_masked_blocks: with unroll, skip fully-masked causal blocks
#     (true causal FLOPs ~ S^2/2 instead of S^2 — hillclimb change #1).
import contextlib as _contextlib
import threading as _threading

_attn_tls = _threading.local()


@_contextlib.contextmanager
def attention_options(unroll: bool = False, skip_masked_blocks: bool = False):
    prev = getattr(_attn_tls, "opts", None)
    _attn_tls.opts = {"unroll": unroll, "skip": skip_masked_blocks}
    try:
        yield
    finally:
        _attn_tls.opts = prev


def _attn_opts():
    return getattr(_attn_tls, "opts", None) or {"unroll": False, "skip": False}


def attn_spec(d_model: int, num_heads: int, num_kv_heads: int, head_dim: int, qk_norm: bool) -> dict:
    spec = {
        "wq": P((d_model, num_heads, head_dim), ("embed", "heads", "head_dim")),
        "wk": P((d_model, num_kv_heads, head_dim), ("embed", "kv_heads", "head_dim")),
        "wv": P((d_model, num_kv_heads, head_dim), ("embed", "kv_heads", "head_dim")),
        "wo": P((num_heads, head_dim, d_model), ("heads", "head_dim", "embed")),
    }
    if qk_norm:
        spec["q_norm"] = {"scale": P((head_dim,), (None,), init="zeros")}
        spec["k_norm"] = {"scale": P((head_dim,), (None,), init="zeros")}
    return spec


def _split_gqa(q, num_kv_heads):
    """(B, S, Hq, D) -> (B, S, Hkv, G, D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, num_kv_heads, hq // num_kv_heads, d)


def _merge_gqa(o):
    b, s, hkv, g, d = o.shape
    return o.reshape(b, s, hkv * g, d)


def _online_block(carry, q, kc, vc, mask, scale):
    """One online-softmax accumulation step.

    q: (B, bq, Hkv, G, D); kc/vc: (B, bk, Hkv, D); mask: (B?, bq, bk) bool.
    carry: (m, l, acc) with m,l: (B, Hkv, G, bq); acc: (B, Hkv, G, bq, D).
    """
    m, l, acc = carry
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, kc, preferred_element_type=jnp.float32)
    s = s * scale
    s = jnp.where(mask[:, None, None, :, :], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[:, None, None, :, :], p, 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    q_chunk: int = 512,
    kv_chunk: int = 512,
    scale: float | None = None,
):
    """Chunked online-softmax attention.

    q: (B, Sq, Hq, D);  k, v: (B, Skv, Hkv, D);  Hq % Hkv == 0.
    ``window > 0`` restricts each query to keys in (pos-window, pos]
    (banded compute: only ceil(window/kv_chunk)+1 KV blocks per Q block).
    Assumes self-attention alignment: query i sits at position
    Skv - Sq + i (supports Sq == Skv; decode uses ``decode_attention``).
    Returns (B, Sq, Hq, D).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    sq_orig = sq
    # Pad to chunk multiples: padded keys sit at positions >= skv, beyond
    # every real query's causal horizon; padded query rows are sliced off.
    if sq % q_chunk:
        pad = q_chunk - sq % q_chunk
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        sq += pad
    if skv % kv_chunk:
        pad = kv_chunk - skv % kv_chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        skv += pad
    if not causal:
        raise NotImplementedError("flash_attention is causal-only")
    nq, nk = sq // q_chunk, skv // kv_chunk
    offset = (skv - (sq - sq_orig)) - sq_orig  # query i at original position offset + i

    qg = _split_gqa(q, hkv)  # (B, Sq, Hkv, G, D)
    g = qg.shape[3]

    opts = _attn_opts()
    if opts["unroll"]:
        return _flash_unrolled(
            qg, k, v, sq_orig, offset, causal, window, q_chunk, kv_chunk, scale,
            skip=opts["skip"],
        ).astype(q.dtype)

    statics = (causal, window, q_chunk, kv_chunk, scale, offset, nk)
    out = _flash_core(statics, qg, k, v)
    return out.reshape(b, sq, hkv * g, d)[:, :sq_orig].astype(q.dtype)


def _block_mask(statics, q_pos, k_pos, b, valid=True):
    causal, window = statics[0], statics[1]
    q_chunk, kv_chunk = q_pos.shape[0], k_pos.shape[0]
    mask = jnp.ones((q_chunk, kv_chunk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    mask &= valid
    return jnp.broadcast_to(mask[None], (b, q_chunk, kv_chunk))


def _kv_blocks_for_q(statics, q_idx, k, v):
    """Yield (kc, vc, k_pos, valid) for the KV blocks a q-chunk touches:
    a static banded set for window attention, all blocks otherwise (the
    caller masks)."""
    causal, window, q_chunk, kv_chunk, scale, offset, nk = statics
    if window > 0:
        band = (window + q_chunk - 1) // kv_chunk + 1
        base = (offset + q_idx * q_chunk) // kv_chunk
        for o in range(band + 1):
            k_idx = base - o
            k_start = jnp.clip(k_idx, 0, nk - 1) * kv_chunk
            kc = jax.lax.dynamic_slice_in_dim(k, k_start, kv_chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, k_start, kv_chunk, axis=1)
            yield kc, vc, k_start + jnp.arange(kv_chunk), k_idx >= 0
    else:
        raise RuntimeError("non-window path uses lax.scan, not this generator")


def _q_block_fwd(statics, qg, k, v, q_idx):
    """One q-chunk of the online-softmax forward.

    Returns (out_block (B, bq, Hkv, G, D), L_block (B, Hkv, G, bq)) where
    L = m + log(l) is the logsumexp needed to rebuild p in the backward."""
    causal, window, q_chunk, kv_chunk, scale, offset, nk = statics
    b, _, hkv, g, d = qg.shape
    qc = jax.lax.dynamic_slice_in_dim(qg, q_idx * q_chunk, q_chunk, axis=1)
    q_pos = offset + q_idx * q_chunk + jnp.arange(q_chunk)
    m0 = jnp.full((b, hkv, g, q_chunk), _NEG, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32)
    if window > 0:
        carry = (m0, l0, a0)
        for kc, vc, k_pos, valid in _kv_blocks_for_q(statics, q_idx, k, v):
            carry = _online_block(
                carry, qc, kc, vc, _block_mask(statics, q_pos, k_pos, b, valid), scale
            )
        m, l, acc = carry
    else:
        ks = k.reshape(b, nk, kv_chunk, hkv, d).swapaxes(0, 1)
        vs = v.reshape(b, nk, kv_chunk, hkv, d).swapaxes(0, 1)

        def kv_step(carry, xs):
            kc, vc, k_idx = xs
            k_pos = k_idx * kv_chunk + jnp.arange(kv_chunk)
            return _online_block(
                carry, qc, kc, vc, _block_mask(statics, q_pos, k_pos, b), scale
            ), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, jnp.arange(nk)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    L = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.transpose(0, 3, 1, 2, 4), L


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_core(statics, qg, k, v):
    """Flash attention with a memory-optimal custom backward.

    Plain AD through the online-softmax scans saves every (bq x bk)
    probability block (O(S^2 / bk) residuals — ~11 GiB/layer at 4k and
    B_loc=1); the custom VJP saves only (q, k, v, o, L) and REBUILDS each
    p block in the backward (FlashAttention's recompute scheme).
    """
    out, _ = _flash_core_fwd(statics, qg, k, v)
    return out


def _flash_core_fwd(statics, qg, k, v):
    causal, window, q_chunk, kv_chunk, scale, offset, nk = statics
    b, sq, hkv, g, d = qg.shape
    nq = sq // q_chunk
    if nq == 1:
        out, L = _q_block_fwd(statics, qg, k, v, jnp.asarray(0))
        Ls = L[:, :, :, None, :]  # (B, Hkv, G, nq=1, bq)
    else:
        out, Ls = jax.lax.map(
            lambda i: _q_block_fwd(statics, qg, k, v, i), jnp.arange(nq)
        )  # out (nq, B, bq, Hkv, G, D); Ls (nq, B, Hkv, G, bq)
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hkv, g, d)
        Ls = Ls.transpose(1, 2, 3, 0, 4)  # (B, Hkv, G, nq, bq)
    out = out.reshape(b, sq, hkv, g, d)
    return out, (qg, k, v, out, Ls)


def _flash_core_bwd(statics, res, dout):
    causal, window, q_chunk, kv_chunk, scale, offset, nk = statics
    qg, k, v, out, Ls = res
    b, sq, hkv, g, d = qg.shape
    nq = sq // q_chunk
    dout = dout.astype(jnp.float32)
    # D_i = rowsum(do * o)  (B, Hkv, G, Sq)
    Drow = jnp.einsum("bshgd,bshgd->bhgs", dout, out.astype(jnp.float32))

    def q_block_bwd(q_idx):
        """Recompute p blockwise; returns (dq_block, dk_partial, dv_partial).

        dk/dv partials are FULL (B, Skv, Hkv, D) accumulators for this
        q-chunk — summed across q-chunks by lax.map+sum below (memory:
        one extra k-sized buffer per live map step)."""
        qc = jax.lax.dynamic_slice_in_dim(qg, q_idx * q_chunk, q_chunk, axis=1)
        doc = jax.lax.dynamic_slice_in_dim(dout, q_idx * q_chunk, q_chunk, axis=1)
        Lc = jax.lax.dynamic_slice_in_dim(
            Ls.reshape(b, hkv, g, sq), q_idx * q_chunk, q_chunk, axis=3
        )
        Dc = jax.lax.dynamic_slice_in_dim(Drow, q_idx * q_chunk, q_chunk, axis=3)
        q_pos = offset + q_idx * q_chunk + jnp.arange(q_chunk)

        dq0 = jnp.zeros((b, q_chunk, hkv, g, d), jnp.float32)
        dk0 = jnp.zeros_like(k, dtype=jnp.float32)
        dv0 = jnp.zeros_like(v, dtype=jnp.float32)

        def one_block(carry, kc, vc, k_pos, k_start, valid):
            dq, dk_full, dv_full = carry
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc, preferred_element_type=jnp.float32) * scale
            mask = _block_mask(statics, q_pos, k_pos, b, valid)
            p = jnp.exp(s - Lc[..., None])
            p = jnp.where(mask[:, None, None, :, :], p, 0.0)
            # dv_j += p^T do ; dp = do v^T ; ds = p * (dp - D) * scale
            dv_blk = jnp.einsum("bhgqk,bqhgd->bkhd", p, doc)
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", doc, vc.astype(jnp.float32))
            ds = p * (dp - Dc[..., None]) * scale
            dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds, kc.astype(jnp.float32))
            dk_blk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qc.astype(jnp.float32))
            dk_full = jax.lax.dynamic_update_slice_in_dim(
                dk_full, jax.lax.dynamic_slice_in_dim(dk_full, k_start, kv_chunk, 1) + dk_blk,
                k_start, axis=1)
            dv_full = jax.lax.dynamic_update_slice_in_dim(
                dv_full, jax.lax.dynamic_slice_in_dim(dv_full, k_start, kv_chunk, 1) + dv_blk,
                k_start, axis=1)
            return dq, dk_full, dv_full

        if window > 0:
            carry = (dq0, dk0, dv0)
            band = (window + q_chunk - 1) // kv_chunk + 1
            base = (offset + q_idx * q_chunk) // kv_chunk
            for o in range(band + 1):
                k_idx = base - o
                k_start = jnp.clip(k_idx, 0, nk - 1) * kv_chunk
                kc = jax.lax.dynamic_slice_in_dim(k, k_start, kv_chunk, axis=1)
                vc = jax.lax.dynamic_slice_in_dim(v, k_start, kv_chunk, axis=1)
                carry = one_block(carry, kc, vc, k_start + jnp.arange(kv_chunk), k_start, k_idx >= 0)
            return carry
        ks = k.reshape(b, nk, kv_chunk, hkv, d).swapaxes(0, 1)
        vs = v.reshape(b, nk, kv_chunk, hkv, d).swapaxes(0, 1)

        def kv_step(carry, xs):
            kc, vc, k_idx = xs
            return one_block(
                carry, kc, vc, k_idx * kv_chunk + jnp.arange(kv_chunk), k_idx * kv_chunk, True
            ), None

        carry, _ = jax.lax.scan(kv_step, (dq0, dk0, dv0), (ks, vs, jnp.arange(nk)))
        return carry

    if nq == 1:
        dq, dk, dv = q_block_bwd(jnp.asarray(0))
        dq_all = dq
    else:
        def step(carry, q_idx):
            dk_acc, dv_acc = carry
            dq, dk, dv = q_block_bwd(q_idx)
            return (dk_acc + dk, dv_acc + dv), dq

        (dk, dv), dqs = jax.lax.scan(
            step,
            (jnp.zeros_like(k, dtype=jnp.float32), jnp.zeros_like(v, dtype=jnp.float32)),
            jnp.arange(nq),
        )  # dqs: (nq, B, bq, Hkv, G, D)
        dq_all = dqs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, hkv, g, d)
    return dq_all.astype(qg.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _flash_unrolled(qg, k, v, sq_orig, offset, causal, window, q_chunk, kv_chunk, scale, skip):
    """Static python-loop flash attention (see ``attention_options``).

    With ``skip`` True, fully-masked blocks are not emitted at all: the
    compiled HLO does the true causal (or banded) FLOPs.
    """
    b, sq, hkv, g, d = qg.shape
    skv = k.shape[1]
    nq, nk = sq // q_chunk, skv // kv_chunk
    outs = []
    for i in range(nq):
        qc = qg[:, i * q_chunk : (i + 1) * q_chunk]
        q_lo = offset + i * q_chunk
        q_hi = q_lo + q_chunk - 1  # inclusive max query position
        q_pos = q_lo + jnp.arange(q_chunk)
        m = jnp.full((b, hkv, g, q_chunk), _NEG, jnp.float32)
        l = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        acc = jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32)
        for j in range(nk):
            k_lo, k_hi = j * kv_chunk, (j + 1) * kv_chunk - 1
            if skip:
                if causal and k_lo > q_hi:
                    continue  # block entirely above the causal diagonal
                if window > 0 and k_hi <= q_lo - window:
                    continue  # block entirely left of the band
            kc = k[:, k_lo : k_hi + 1]
            vc = v[:, k_lo : k_hi + 1]
            k_pos = k_lo + jnp.arange(kv_chunk)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            mask = jnp.broadcast_to(mask[None], (b, q_chunk, kv_chunk))
            m, l, acc = _online_block((m, l, acc), qc, kc, vc, mask, scale)
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(out.transpose(0, 3, 1, 2, 4))  # (B, bq, Hkv, G, D)
    out = jnp.concatenate(outs, axis=1).reshape(b, sq, hkv * g, d)
    return out[:, :sq_orig]


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0, scale=None):
    """Single-token attention against a (possibly partially filled) cache.

    q: (B, 1, Hq, D);  k_cache/v_cache: (B, Smax, Hkv, D);
    cache_len: scalar int — number of valid positions (the new token's KV
    must already be written at cache_len - 1).
    """
    b, _, hq, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    scale = scale if scale is not None else d ** -0.5
    qg = _split_gqa(q, hkv)  # (B, 1, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    pos = jnp.arange(smax)
    mask = pos[None, :] < cache_len
    if window > 0:
        mask &= pos[None, :] > cache_len - 1 - window
    s = jnp.where(mask[:, None, None, None, :] if mask.ndim == 2 else mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache)
    return _merge_gqa(o).astype(q.dtype)


# ------------------------------------------------------------------ module


def _project_qkv(params, x, cfg, positions, theta):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if "q_norm" in params:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.position_embedding == "rope":
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v


def attn_forward(params, x, cfg, *, window: int = 0, theta: float = 10_000.0, positions=None):
    """Full-sequence causal attention.  Returns (y, (k, v)) for cache build."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    q, k, v = _project_qkv(params, x, cfg, positions, theta)
    q = shard_act(q, "act_heads")
    k = shard_act(k, "act_kv_heads")
    v = shard_act(v, "act_kv_heads")
    o = flash_attention(
        q, k, v, causal=True, window=window,
        q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk,
        scale=cfg.attention_multiplier or None,
    )
    y = jnp.einsum("bthk,hkd->btd", o, params["wo"])
    return y, (k, v)


def attn_decode(params, x, kv_cache, pos, cfg, *, window: int = 0, theta: float = 10_000.0):
    """One decode step.  x: (B, 1, D); kv_cache: (k, v) each (B, Smax, Hkv, Dh);
    pos: scalar int32 — current position (0-based) of the new token.
    Returns (y, new_kv_cache)."""
    k_cache, v_cache = kv_cache
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None, None] if jnp.ndim(pos) == 0 else pos[:, None], (b, 1))
    q, k, v = _project_qkv(params, x, cfg, positions, theta)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, axis=1)
    o = decode_attention(q, k_cache, v_cache, pos + 1, window=window,
                         scale=cfg.attention_multiplier or None)
    y = jnp.einsum("bthk,hkd->btd", o, params["wo"])
    return y, (k_cache, v_cache)
