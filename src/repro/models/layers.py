"""Common transformer layers: norms, RoPE, MLPs, embeddings.

Pure functions over param pytrees (specs in ``repro.models.spec``).
Activation sharding uses ``repro.distributed.sharding.shard_act`` logical
annotations; outside a mesh context these are no-ops.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.spec import P

__all__ = [
    "rmsnorm_spec", "rmsnorm",
    "rope", "rope_decode",
    "mlp_spec", "mlp",
    "embed_spec", "embed_tokens", "logits_from_embed",
    "softcap",
]

# ---------------------------------------------------------------- norms


def rmsnorm_spec(dim: int) -> dict:
    return {"scale": P((dim,), (None,), init="zeros")}  # gemma-style (1+scale)


def rmsnorm(params, x, eps: float = 1e-6):
    """RMSNorm with (1 + scale) parameterization (Gemma/Griffin convention;
    scale init zeros => identity at init, matching ones-init classic form)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    out = x * (1.0 + params["scale"].astype(jnp.float32))
    return out.astype(dtype)


# ---------------------------------------------------------------- rope


def _rope_freqs(head_dim: int, theta: float):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # (head_dim/2,)


def rope(x, positions, theta: float = 10_000.0):
    """Apply rotary embedding.  x: (..., S, H, Dh); positions: (..., S)."""
    freqs = _rope_freqs(x.shape[-1], theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    angles = angles[..., None, :]  # broadcast over heads: (..., S, 1, Dh/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rope_decode(x, position, theta: float = 10_000.0):
    """RoPE for a single decode step.  x: (B, 1, H, Dh); position: (B,) or scalar."""
    pos = jnp.asarray(position)
    if pos.ndim == 0:
        pos = pos[None]
    return rope(x, pos[:, None], theta)


# ---------------------------------------------------------------- mlp


def mlp_spec(d_model: int, d_ff: int, gated: bool) -> dict:
    if gated:
        return {
            "w_gate": P((d_model, d_ff), ("embed", "ffn")),
            "w_up": P((d_model, d_ff), ("embed", "ffn")),
            "w_down": P((d_ff, d_model), ("ffn", "embed")),
        }
    return {
        "w_up": P((d_model, d_ff), ("embed", "ffn")),
        "w_down": P((d_ff, d_model), ("ffn", "embed")),
    }


def _act(name: str, x):
    if name in ("swiglu", "silu"):
        return jax.nn.silu(x)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name!r}")


def mlp(params, x, activation: str = "swiglu"):
    """(Gated) MLP.  x: (..., d_model)."""
    if "w_gate" in params:
        h = _act(activation, x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = _act(activation, x @ params["w_up"])
    h = shard_act(h, "act_ffn")
    return h @ params["w_down"]


# ---------------------------------------------------------------- embeddings


def embed_spec(vocab: int, d_model: int) -> dict:
    return {"embedding": P((vocab, d_model), ("vocab", "embed"), init="small")}


def embed_tokens(params, tokens, scale_by_dim: bool = False):
    """Token embedding lookup: one gather from the (vocab, d_model) table.

    Under a mesh with ``Auto`` axes (``launch.mesh.make_mesh``) the
    partitioner resolves a vocab-sharded table with a collective."""
    table = params["embedding"]
    x = table[tokens]
    if scale_by_dim:
        x = x * jnp.asarray(jnp.sqrt(table.shape[-1]), x.dtype)
    return x


def logits_from_embed(params, x, softcap_value: float = 0.0):
    """Tied-embedding readout: (..., D) @ (V, D)^T -> (..., V)."""
    logits = x @ params["embedding"].T
    logits = shard_act(logits, "logits")
    if softcap_value and softcap_value > 0:
        logits = softcap(logits, softcap_value)
    return logits


def softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap
