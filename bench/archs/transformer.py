"""Decoder-only transformer: GQA attention with RoPE and a SwiGLU or GELU MLP
(granite-8b, musicgen-medium's decoder stack).

A group holds its published config's keys: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``intermediate_size``, ``hidden_act``, ``rope_theta``,
``rms_norm_eps``, ``vocab_size`` and ``model_name``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import HI, mm, rms, rope, scan_layers, tied_logits

# Widest normalized gap allowed for a served token.  Set from TPU v5e
# readings over a dozen seeds and more: the program at most 0.0615, the
# float8 control at least 0.256 (PERF.md).
GAP_LIMIT = 0.15


@dataclasses.dataclass(frozen=True)
class Dims:
    """Shapes of one served transformer."""

    name: str
    layers: int
    d: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    act: str = "silu"  # silu: gated SwiGLU MLP; gelu: plain GELU MLP
    rope_theta: float = 10_000.0
    eps: float = 1e-6

    @property
    def gated(self) -> bool:
        return self.act == "silu"


def dims(c: dict) -> Dims:
    """``Dims`` of one model group of a configuration file."""
    return Dims(
        name=c["model_name"], layers=c["num_hidden_layers"], d=c["hidden_size"],
        vocab=c["vocab_size"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ff=c["intermediate_size"], act=c["hidden_act"], rope_theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]),
    )


def model_config(dims: Dims):
    """The program's ``ModelConfig`` for these sizes (bf16, as served)."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=dims.name, family="dense", num_layers=dims.layers, d_model=dims.d,
        vocab_size=dims.vocab, num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, d_ff=dims.ff,
        activation="swiglu" if dims.gated else "gelu", pattern=("attn:mlp",),
        rope_theta=dims.rope_theta, tie_embeddings=True,
    )


def param_layout(dims: Dims) -> dict:
    """Nested {name: (shape, law, std)} in the program's parameter layout
    (laws: ``bench.weights``)."""
    L, d = dims.layers, dims.d
    H, Hkv, Dh, F = dims.heads, dims.kv_heads, dims.head_dim, dims.ff
    mlp = {
        "w_up": ((L, d, F), "normal", d ** -0.5),
        "w_down": ((L, F, d), "normal", F ** -0.5),
    }
    if dims.gated:
        mlp["w_gate"] = ((L, d, F), "normal", d ** -0.5)
    block = {
        "pre_norm": {"scale": ((L, d), "scale", 0.1)},
        "attn": {
            "wq": ((L, d, H, Dh), "normal", d ** -0.5),
            "wk": ((L, d, Hkv, Dh), "normal", d ** -0.5),
            "wv": ((L, d, Hkv, Dh), "normal", d ** -0.5),
            "wo": ((L, H, Dh, d), "normal", (H * Dh) ** -0.5),
        },
        "mlp_norm": {"scale": ((L, d), "scale", 0.1)},
        "mlp": mlp,
    }
    return {
        "embed": {"embedding": ((dims.vocab, d), "normal", 0.02)},
        "blocks": [block],
        "tail": [],
        "final_norm": {"scale": ((d,), "scale", 0.1)},
    }


def _layer_linear(dims: Dims) -> int:
    """Multiply-adds of one layer's linear maps for one token."""
    d, H, Hkv, Dh, F = dims.d, dims.heads, dims.kv_heads, dims.head_dim, dims.ff
    return d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d + (3 if dims.gated else 2) * d * F


def param_count(dims: Dims) -> int:
    """Parameters of the served model, from its shapes."""
    return dims.vocab * dims.d + dims.d + dims.layers * (2 * dims.d + _layer_linear(dims))


def token_flops(dims: Dims, context: int, logits: bool) -> int:
    """Operations for one token that attends to ``context`` positions
    (itself included); ``logits`` adds the readout over the vocabulary."""
    per_layer = 2 * _layer_linear(dims) + 4 * dims.heads * dims.head_dim * context  # q.k, p.v
    return dims.layers * per_layer + (2 * dims.d * dims.vocab if logits else 0)


def forward_flops(dims: Dims, rows: int, prompt_len: int, new_tokens: int) -> int:
    """Useful operations of one served forward (``bench.flops.forward_flops``)."""
    return flops.tokens_forward(token_flops, dims, rows, prompt_len, new_tokens)


def state_bytes(dims: Dims, batch: int, context: int) -> int:
    """Bytes of KV cache a decode step reads (``context`` positions) and
    writes (the new one) for ``batch`` rows."""
    kv = batch * context * dims.kv_heads * dims.head_dim * flops.BF16 * 2  # keys and values
    new = batch * dims.kv_heads * dims.head_dim * flops.BF16 * 2
    return dims.layers * (kv + new)


def decode_step_cost(dims: Dims, batch: int, context: int) -> tuple[int, int]:
    """(operations, bytes) of one decode step: every bf16 weight is read
    once, and the KV cache is read and the new entry written."""
    ops = batch * token_flops(dims, context, logits=True)
    return ops, param_count(dims) * flops.BF16 + state_bytes(dims, batch, context)


def _layer(dims: Dims, quant: bool, x, p):
    a = p["attn"]
    h = rms(x, p["pre_norm"]["scale"], dims.eps)
    q = mm("bsd,dhk->bshk", h, a["wq"], quant, (-1,), (0,))
    k = mm("bsd,dhk->bshk", h, a["wk"], quant, (-1,), (0,))
    v = mm("bsd,dhk->bshk", h, a["wv"], quant, (-1,), (0,))
    q, k = rope(q, dims.rope_theta), rope(k, dims.rope_theta)
    group = dims.heads // dims.kv_heads  # query head i reads kv head i // group
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / jnp.sqrt(float(dims.head_dim))
    n = x.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + mm("bqhk,hkd->bqd", o, a["wo"], quant, (-2, -1), (0, 1))
    h = rms(x, p["mlp_norm"]["scale"], dims.eps)
    m = p["mlp"]
    up = mm("bsd,df->bsf", h, m["w_up"], quant, (-1,), (0,))
    if dims.gated:
        up = jax.nn.silu(mm("bsd,df->bsf", h, m["w_gate"], quant, (-1,), (0,))) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + mm("bsf,fd->bsd", up, m["w_down"], quant, (-1,), (0,))


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(dims: Dims, params, tokens, quant: bool = False):
    """(B, S, V) float32 reference logits of ``tokens`` (B, S) at every
    position: the whole sequence under an explicit causal mask, no cache."""
    layer = functools.partial(_layer, dims, quant)
    return tied_logits(params, tokens, quant, dims.eps,
                       lambda x: scan_layers(layer, params["blocks"][0], x))
