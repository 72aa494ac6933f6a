"""Full-model assembly: embed -> scanned layer periods -> tail -> norm -> logits.

Layers are grouped into the config's repeating pattern period; all full
periods run under one ``lax.scan`` with params (and caches) stacked on a
leading "layers" axis — keeping HLO size ~1 period regardless of depth
(essential for the 512-way SPMD dry-run compile matrix).  Remainder
layers are unrolled.  ``remat`` wraps the period body in jax.checkpoint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models import blocks
from repro.models.layers import embed_spec, embed_tokens, logits_from_embed, rmsnorm, rmsnorm_spec
from repro.models.spec import P, stack

__all__ = ["model_spec", "forward", "prefill", "decode_step", "loss_fn"]


def model_spec(cfg) -> dict:
    spec: dict = {"embed": embed_spec(cfg.vocab_size, cfg.d_model)}
    spec["blocks"] = [
        stack(blocks.block_spec(cfg, kind), cfg.n_periods) for kind in cfg.pattern
    ]
    spec["tail"] = [
        blocks.block_spec(cfg, cfg.layer_kind(cfg.n_periods * cfg.period + i))
        for i in range(cfg.n_tail)
    ]
    spec["final_norm"] = rmsnorm_spec(cfg.d_model)
    if not cfg.tie_embeddings:
        spec["lm_head"] = P((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="small")
    return spec


def _tail_kinds(cfg):
    return [cfg.layer_kind(cfg.n_periods * cfg.period + i) for i in range(cfg.n_tail)]


def _logits(params, cfg, x):
    table = {"embedding": params["lm_head"] if "lm_head" in params else params["embed"]["embedding"]}
    if cfg.logits_scaling != 1.0:
        x = x / cfg.logits_scaling
    return logits_from_embed(table, x, cfg.logit_softcap)


def _embed(params, cfg, tokens):
    x = embed_tokens(params["embed"], tokens, scale_by_dim=cfg.embed_scale)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return shard_act(x.astype(dtype), "act_btd")


# ------------------------------------------------------------------ full


def forward(params, tokens, cfg):
    """Causal LM forward.  tokens: (B, S) int32 -> (logits (B, S, V), aux)."""
    x, aux = hidden_states(params, tokens, cfg)
    return _logits(params, cfg, x), aux


def hidden_states(params, tokens, cfg):
    """Embed + blocks + final norm, WITHOUT the logits projection."""
    x = _embed(params, cfg, tokens)

    def period_body(x, period_params):
        aux = jnp.zeros((), jnp.float32)
        for p_idx, kind in enumerate(cfg.pattern):
            x = shard_act(x, "act_btd")
            x, a = blocks.block_full(period_params[p_idx], x, cfg, kind)
            aux = aux + a
        return x, aux

    body = period_body
    if cfg.remat:
        body = jax.checkpoint(period_body, prevent_cse=False)
    if cfg.n_periods > 0:
        x, auxs = jax.lax.scan(lambda c, p: body(c, p), x, params["blocks"])
        aux = auxs.sum()
    else:
        aux = jnp.zeros((), jnp.float32)
    for tp, kind in zip(params["tail"], _tail_kinds(cfg)):
        x, a = blocks.block_full(tp, x, cfg, kind)
        aux = aux + a
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def chunked_xent(x, table, targets, mask, softcap_value: float, chunk: int):
    """Cross-entropy over sequence chunks: full (B, S, V) logits are never
    materialized (the bwd pass would otherwise keep several fp32 copies).
    The chunk body is rematerialized, so only the (B, c, D) slices are
    saved across the scan."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xs = (
        x.reshape(b, nc, chunk, d).swapaxes(0, 1),
        targets.reshape(b, nc, chunk).swapaxes(0, 1),
        mask.reshape(b, nc, chunk).swapaxes(0, 1),
    )

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(total, xs):
        xc, tc, mc = xs
        xc = shard_act(xc, "xent_act")
        logits = (xc @ table.T).astype(jnp.float32)
        logits = shard_act(logits, "logits")
        if softcap_value and softcap_value > 0:
            logits = jnp.tanh(logits / softcap_value) * softcap_value
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return total + ((logz - gold) * mc).sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    return total


def loss_fn(params, batch, cfg):
    """Next-token cross-entropy via chunked logits (memory-bounded).

    batch: {"tokens": (B, S) int32, optional "mask": (B, S)}.
    Returns (loss, metrics)."""
    tokens = batch["tokens"]
    x, aux = hidden_states(params, tokens[:, :-1], cfg)
    if cfg.logits_scaling != 1.0:
        x = x / cfg.logits_scaling
    targets = tokens[:, 1:]
    mask = batch.get("mask")
    mask = jnp.ones_like(targets, jnp.float32) if mask is None else mask[:, 1:].astype(jnp.float32)
    table = params["lm_head"] if "lm_head" in params else params["embed"]["embedding"]
    nll = chunked_xent(x, table, targets, mask, cfg.logit_softcap, cfg.xent_chunk)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll / denom
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux, "tokens": denom}


# ------------------------------------------------------------------ prefill


def _total(counts):
    """Sum of the layers' counters (None where no layer counts)."""
    counts = [c for c in counts if c is not None]
    return functools.reduce(jnp.add, counts) if counts else None


def _scanned_total(counts):
    """``_total`` of counters stacked over the scanned periods."""
    return None if counts is None else counts.sum(axis=0)


def prefill(params, tokens, cfg, max_len: int, counts: bool = False):
    """Process a full prompt; returns (last_logits (B, V), cache), and with
    ``counts`` the held-expert MoE layers' counters summed over the layers
    (int32 (2,): routed pairs, experts reached; None without such layers).

    cache = {"blocks": [stacked per pattern position], "tail": [...],
             "pos": scalar int32 (= prompt length)}."""
    x = _embed(params, cfg, tokens)

    def period_body(x, period_params):
        caches, n = [], []
        for p_idx, kind in enumerate(cfg.pattern):
            x = shard_act(x, "act_btd")
            x, cache, c = blocks.block_prefill(period_params[p_idx], x, cfg, kind, max_len)
            caches.append(cache)
            n.append(c)
        return x, (caches, _total(n))

    body = period_body
    if cfg.remat:
        body = jax.checkpoint(period_body, prevent_cse=False)
    if cfg.n_periods > 0:
        x, (block_caches, n) = jax.lax.scan(lambda c, p: body(c, p), x, params["blocks"])
        n = [_scanned_total(n)]
    else:
        block_caches, n = [], []
    tail_caches = []
    for tp, kind in zip(params["tail"], _tail_kinds(cfg)):
        x, cache, c = blocks.block_prefill(tp, x, cfg, kind, max_len)
        tail_caches.append(cache)
        n.append(c)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x[:, -1, :])
    cache = {
        "blocks": block_caches,
        "tail": tail_caches,
        "pos": jnp.asarray(tokens.shape[1], jnp.int32),
    }
    return (logits, cache, _total(n)) if counts else (logits, cache)


# ------------------------------------------------------------------ decode


def decode_step(params, cache, tokens, cfg, counts: bool = False):
    """One decode step.  tokens: (B, 1) int32; cache from ``prefill`` (or
    ``repro.models.kvcache.init_cache``).  Returns (logits (B, V), cache),
    and with ``counts`` the step's counters as ``prefill`` gives them."""
    pos = cache["pos"]
    x = _embed(params, cfg, tokens)

    def period_body(x, xs):
        period_params, period_cache = xs
        new_caches, n = [], []
        for p_idx, kind in enumerate(cfg.pattern):
            x, c, k = blocks.block_decode(
                period_params[p_idx], x, period_cache[p_idx], pos, cfg, kind
            )
            new_caches.append(c)
            n.append(k)
        return x, (new_caches, _total(n))

    if cfg.n_periods > 0:
        x, (new_block_caches, n) = jax.lax.scan(
            period_body, x, (params["blocks"], cache["blocks"])
        )
        n = [_scanned_total(n)]
    else:
        new_block_caches, n = [], []
    new_tail = []
    for tp, tc, kind in zip(params["tail"], cache["tail"], _tail_kinds(cfg)):
        x, c, k = blocks.block_decode(tp, x, tc, pos, cfg, kind)
        new_tail.append(c)
        n.append(k)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x[:, -1, :])
    cache = {"blocks": new_block_caches, "tail": new_tail, "pos": pos + 1}
    return (logits, cache, _total(n)) if counts else (logits, cache)
