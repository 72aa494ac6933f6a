"""Per-layer blocks + the cache protocol shared by all mixer kinds.

A block = pre-norm mixer + residual [+ pre-norm FFN + residual], with
optional gemma3-style post-norms; each mixer and FFN output is scaled by
``cfg.residual_multiplier`` before its residual add.  Three entry points
per block:

  * ``block_full``    — full sequence, no cache (training / scoring)
  * ``block_prefill`` — full sequence, returns the decode cache
  * ``block_decode``  — one token, consumes + returns the cache

The serving entry points also return the FFN's counters: a held-expert MoE
layer's int32 (routed pairs, experts reached), None for every other layer.

Cache layouts (per layer):
  attn:   {"k","v"}: (B, max_len, Hkv, Dh)     — absolute slots
  local:  {"k","v"}: (B, window, Hkv, Dh)      — ring buffer, slot = pos % window
  rglru:  {"conv": (B, W-1, lru), "h": (B, lru)}
  ssd:    {"conv": (B, W-1, d_xbc), "state": (B, H, P, N)}
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.models.layers import mlp, mlp_spec, rmsnorm, rmsnorm_spec

__all__ = ["block_spec", "cache_spec", "block_full", "block_prefill", "block_decode"]


def _mixer(kind: str) -> str:
    return kind.partition(":")[0]


def _ffn(kind: str) -> str:
    return kind.partition(":")[2]


def block_spec(cfg, kind: str) -> dict:
    mixer, ffn = _mixer(kind), _ffn(kind)
    d = cfg.d_model
    spec: dict = {"pre_norm": rmsnorm_spec(d)}
    if mixer in ("attn", "local"):
        spec["attn"] = attn_mod.attn_spec(
            d, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.qk_norm
        )
    elif mixer == "rglru":
        spec["rec"] = rglru_mod.rglru_spec(cfg)
    elif mixer == "ssd":
        spec["ssd"] = ssd_mod.ssd_spec(cfg)
    if cfg.post_norms:
        spec["post_norm"] = rmsnorm_spec(d)
    if ffn != "none":
        spec["mlp_norm"] = rmsnorm_spec(d)
        gated = cfg.activation in ("swiglu", "geglu")
        if ffn == "mlp":
            spec["mlp"] = mlp_spec(d, cfg.dense_d_ff, gated)
        else:
            spec["moe"] = moe_mod.moe_spec(d, cfg.num_experts, cfg.moe_d_ff, gated,
                                           cfg.shared_expert, cfg.experts_here, cfg.shared_d_ff)
        if cfg.post_norms:
            spec["mlp_post_norm"] = rmsnorm_spec(d)
    return spec


def cache_spec(cfg, kind: str, batch: int, max_len: int) -> dict:
    """Shape/dtype template (dict of (shape, dtype)) for one layer's cache."""
    mixer = _mixer(kind)
    kv_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if mixer in ("attn", "local"):
        length = max_len if mixer == "attn" else min(cfg.window_size, max_len)
        shp = (batch, length, cfg.num_kv_heads, cfg.head_dim)
        if cfg.kv_quant:
            sshp = shp[:-1] + (1,)
            return {"k": (shp, jnp.int8), "k_scale": (sshp, jnp.float32),
                    "v": (shp, jnp.int8), "v_scale": (sshp, jnp.float32)}
        return {"k": (shp, kv_dtype), "v": (shp, kv_dtype)}
    if mixer == "rglru":
        conv, h = rglru_mod.rglru_init_cache_shapes(cfg, batch)
        return {"conv": (conv, kv_dtype), "h": (h, jnp.float32)}
    if mixer == "ssd":
        conv, st = ssd_mod.ssd_init_cache_shapes(cfg, batch)
        return {"conv": (conv, kv_dtype), "state": (st, jnp.float32)}
    raise ValueError(kind)


def _theta(cfg, mixer: str) -> float:
    return cfg.rope_theta_local if mixer == "local" else cfg.rope_theta


# --------------------------------------------------------- int8 KV cache

def _kv_quant(x):
    """(B, S, H, D) -> (int8 codes, (B, S, H, 1) fp32 scales)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _store_kv(cfg, k, v, packer):
    """Build a cache dict through ``packer(tensor) -> stored layout``."""
    kv_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if cfg.kv_quant:
        qk, sk = _kv_quant(k)
        qv, sv = _kv_quant(v)
        return {"k": packer(qk), "k_scale": packer(sk),
                "v": packer(qv), "v_scale": packer(sv)}
    return {"k": packer(k.astype(kv_dtype)), "v": packer(v.astype(kv_dtype))}


def _read_kv(cfg, cache, dtype):
    if cfg.kv_quant:
        return (_kv_dequant(cache["k"], cache["k_scale"], dtype),
                _kv_dequant(cache["v"], cache["v_scale"], dtype))
    return cache["k"], cache["v"]


# ------------------------------------------------------------------ ffn part


def _scaled(y, cfg):
    """A mixer or FFN output as it joins the residual stream."""
    return y * cfg.residual_multiplier if cfg.residual_multiplier != 1.0 else y


def _apply_ffn(params, x, cfg, kind):
    """The FFN half of a block: (x, aux, counts), ``aux`` the GShard layer's
    load-balancing loss (else 0) and ``counts`` a held-expert layer's
    counters (else None)."""
    ffn = _ffn(kind)
    aux, counts = jnp.zeros((), jnp.float32), None
    if ffn == "none":
        return x, aux, counts
    h = rmsnorm(params["mlp_norm"], x, cfg.norm_eps)
    if ffn == "mlp":
        y = mlp(params["mlp"], h, cfg.activation)
    elif cfg.moe_router == "topk_softmax":
        y, counts = moe_mod.held_forward(params["moe"], h, cfg)
    else:
        y, aux = moe_mod.moe_forward(params["moe"], h, cfg)
    if cfg.post_norms:
        y = rmsnorm(params["mlp_post_norm"], y, cfg.norm_eps)
    return x + _scaled(y, cfg), aux, counts


def _post(params, y, cfg):
    y = rmsnorm(params["post_norm"], y, cfg.norm_eps) if cfg.post_norms else y
    return _scaled(y, cfg)


# ------------------------------------------------------------------ full


def block_full(params, x, cfg, kind: str):
    """Training/scoring pass (no cache).  Returns (x, aux)."""
    mixer = _mixer(kind)
    h = rmsnorm(params["pre_norm"], x, cfg.norm_eps)
    if mixer in ("attn", "local"):
        window = cfg.window_size if mixer == "local" else 0
        y, _ = attn_mod.attn_forward(
            params["attn"], h, cfg, window=window, theta=_theta(cfg, mixer)
        )
    elif mixer == "rglru":
        y, _ = rglru_mod.rglru_forward(params["rec"], h, cfg)
    else:  # ssd
        y, _ = ssd_mod.ssd_forward(params["ssd"], h, cfg)
    x = x + _post(params, y, cfg)
    x, aux, _ = _apply_ffn(params, x, cfg, kind)
    return x, aux


# ------------------------------------------------------------------ prefill


def _ring_from_prefill(k, window: int, max_len: int):
    """Pack full-sequence keys (B, S, H, D) into the ring-buffer layout.

    Slot p %% window holds position p, for the last ``window`` positions."""
    b, s, hkv, dh = k.shape
    w = min(window, max_len)
    if s < w:
        buf = jnp.zeros((b, w, hkv, dh), k.dtype)
        return jax.lax.dynamic_update_slice_in_dim(buf, k, 0, axis=1)
    last = k[:, s - w :, :, :]
    # position (s - w + j) -> slot (s - w + j) % w: a static roll.
    return jnp.roll(last, shift=(s - w) % w, axis=1)


def block_prefill(params, x, cfg, kind: str, max_len: int):
    """Full-sequence pass that also builds the decode cache.

    Returns (x, cache, counts)."""
    mixer = _mixer(kind)
    h = rmsnorm(params["pre_norm"], x, cfg.norm_eps)
    if mixer in ("attn", "local"):
        window = cfg.window_size if mixer == "local" else 0
        y, (k, v) = attn_mod.attn_forward(
            params["attn"], h, cfg, window=window, theta=_theta(cfg, mixer)
        )
        if mixer == "attn":
            def pack(t):
                b_, s_ = t.shape[:2]
                buf = jnp.zeros((b_, max_len) + t.shape[2:], t.dtype)
                return jax.lax.dynamic_update_slice_in_dim(buf, t, 0, axis=1)
        else:
            def pack(t):
                return _ring_from_prefill(t, cfg.window_size, max_len)
        cache = _store_kv(cfg, k, v, pack)
    elif mixer == "rglru":
        y, (conv, hlast) = rglru_mod.rglru_forward(params["rec"], h, cfg)
        cache = {"conv": conv, "h": hlast}
    else:  # ssd
        y, (conv, state) = ssd_mod.ssd_forward(params["ssd"], h, cfg)
        cache = {"conv": conv, "state": state}
    x = x + _post(params, y, cfg)
    x, _, counts = _apply_ffn(params, x, cfg, kind)
    return x, cache, counts


# ------------------------------------------------------------------ decode


def block_decode(params, x, cache, pos, cfg, kind: str):
    """One-token step.  x: (B, 1, D); pos: scalar int32 (position of the
    new token).  Returns (x, new_cache, counts)."""
    mixer = _mixer(kind)
    h = rmsnorm(params["pre_norm"], x, cfg.norm_eps)
    if mixer in ("attn", "local"):
        is_ring = mixer == "local"
        length = cache["k"].shape[1]
        slot = jnp.mod(pos, length) if is_ring else pos
        b = x.shape[0]
        positions = jnp.broadcast_to(
            pos[None, None] if jnp.ndim(pos) == 0 else pos[:, None], (b, 1))
        q, k, v = attn_mod._project_qkv(
            params["attn"], h, cfg, positions, _theta(cfg, mixer)
        )
        new_slot = _store_kv(cfg, k, v, lambda t: t)
        new_cache = {
            name: jax.lax.dynamic_update_slice_in_dim(
                cache[name], new_slot[name].astype(cache[name].dtype), slot, axis=1)
            for name in cache
        }
        kc, vc = _read_kv(cfg, new_cache, q.dtype)
        valid = jnp.minimum(pos + 1, length) if is_ring else pos + 1
        o = attn_mod.decode_attention(q, kc, vc, valid, window=0,
                                      scale=cfg.attention_multiplier or None)
        y = jnp.einsum("bthk,hkd->btd", o, params["attn"]["wo"])
    elif mixer == "rglru":
        y, (conv, hs) = rglru_mod.rglru_decode_step(params["rec"], h, (cache["conv"], cache["h"]), cfg)
        new_cache = {"conv": conv, "h": hs}
    else:  # ssd
        y, (conv, state) = ssd_mod.ssd_decode_step(params["ssd"], h, (cache["conv"], cache["state"]), cfg)
        new_cache = {"conv": conv, "state": state}
    x = x + _post(params, y, cfg)
    x, _, counts = _apply_ffn(params, x, cfg, kind)
    return x, new_cache, counts
