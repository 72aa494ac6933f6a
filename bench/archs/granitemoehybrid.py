"""Granite 4.0-H: Mamba-2 and NoPE attention layers, each with a top-k-softmax
MoE FFN and a shared expert, under Granite's four multipliers
(granite-4.0-h-small).

A group holds its published config's keys: ``hidden_size``,
``num_hidden_layers``, ``layer_types`` (the first ``num_hidden_layers`` of
them are run), ``num_attention_heads``, ``num_key_value_heads``,
``attention_multiplier``, ``position_embedding_type``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
``num_local_experts`` (the experts held here), ``num_experts_per_tok``,
``intermediate_size`` (one expert's width), ``shared_intermediate_size``,
``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``rms_norm_eps``, ``vocab_size`` and ``model_name``; and under
``deployment`` the router's width (``router_experts``) and the first held
expert (``expert_offset``).

The expert layer of this chip: the router scores all ``router_experts``,
the softmax runs over each token's top ``num_experts_per_tok`` logits, and
the held experts' gated outputs plus the shared expert's make the result.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import HI, mm, rms, scan_layers, tied_logits

# Widest normalized gap allowed for a served token.  Set from TPU v5e
# readings at the published widths (PERF.md): the program at most 0.141 over
# six seeds, the float8 control at least 0.996; planted departures at least
# 0.335 (residual multiplier 1.0), 0.489 (RoPE on), 4.57 (shared expert
# dropped).
GAP_LIMIT = 0.3

# The program scans the SSD in chunks of this many positions (the prompt's
# length); the scan is exact for any chunk (the configuration's departures).
SSD_CHUNK = 8


@dataclasses.dataclass(frozen=True)
class Dims:
    """Shapes of one served Granite 4.0-H stage on this chip."""

    name: str
    layers: int
    d: int
    vocab: int
    kinds: tuple  # "mamba" or "attention", one per layer run
    period: tuple  # the kinds of one period of the published layer_types
    heads: int
    kv_heads: int
    head_dim: int
    attn_scale: float
    rope: bool
    ssd_heads: int
    headdim: int
    d_state: int
    ngroups: int
    d_conv: int
    experts: int  # the router's width
    held: int  # experts held here
    offset: int  # the first of them
    top_k: int
    ff: int
    shared_ff: int
    embed_mult: float
    residual_mult: float
    logits_div: float
    eps: float

    @property
    def d_inner(self) -> int:
        return self.ssd_heads * self.headdim

    @property
    def d_xbc(self) -> int:
        return self.d_inner + 2 * self.ngroups * self.d_state


def _period(kinds) -> tuple:
    """The shortest repeating head of ``kinds``."""
    n = next(n for n in range(1, len(kinds) + 1)
             if all(k == kinds[i % n] for i, k in enumerate(kinds)))
    return tuple(kinds[:n])


def dims(c: dict) -> Dims:
    """``Dims`` of one model group of a configuration file; raises where the
    program cannot build the model (``model_config``), before any weight."""
    if c["position_embedding_type"] not in ("nope", "rope"):
        raise ValueError(f"position_embedding_type {c['position_embedding_type']!r}")
    dep = c["deployment"]
    d = Dims(
        name=c["model_name"], layers=c["num_hidden_layers"], d=c["hidden_size"],
        vocab=c["vocab_size"], kinds=tuple(c["layer_types"][:c["num_hidden_layers"]]),
        period=_period(c["layer_types"]),
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        attn_scale=float(c["attention_multiplier"]),
        rope=c["position_embedding_type"] == "rope",
        ssd_heads=c["mamba_n_heads"], headdim=c["mamba_d_head"], d_state=c["mamba_d_state"],
        ngroups=c["mamba_n_groups"], d_conv=c["mamba_d_conv"],
        experts=dep["router_experts"], held=c["num_local_experts"], offset=dep["expert_offset"],
        top_k=c["num_experts_per_tok"], ff=c["intermediate_size"],
        shared_ff=c["shared_intermediate_size"],
        embed_mult=float(c["embedding_multiplier"]), residual_mult=float(c["residual_multiplier"]),
        logits_div=float(c["logits_scaling"]), eps=float(c["rms_norm_eps"]),
    )
    model_config(d)
    return d


def model_config(dims: Dims):
    """The program's ``ModelConfig`` for these sizes (bf16, as served)."""
    from repro.configs.base import ModelConfig

    mixer = {"mamba": "ssd", "attention": "attn"}
    return ModelConfig(
        name=dims.name, family="hybrid", num_layers=dims.layers, d_model=dims.d,
        vocab_size=dims.vocab, num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, position_embedding="rope" if dims.rope else "none",
        attention_multiplier=dims.attn_scale, activation="swiglu",
        pattern=tuple(f"{mixer[k]}:moe" for k in dims.period), tie_embeddings=True,
        embedding_multiplier=dims.embed_mult, residual_multiplier=dims.residual_mult,
        logits_scaling=dims.logits_div, norm_eps=dims.eps,
        num_experts=dims.experts, moe_top_k=dims.top_k, moe_d_ff=dims.ff,
        shared_expert=True, shared_d_ff=dims.shared_ff, moe_router="topk_softmax",
        experts_here=dims.held, expert_offset=dims.offset,
        ssd_state=dims.d_state, ssd_headdim=dims.headdim,
        ssd_expand=dims.d_inner // dims.d, ssd_ngroups=dims.ngroups, ssd_chunk=SSD_CHUNK,
        conv_width=dims.d_conv,
    )


def _block(dims: Dims, kind: str, lead: tuple) -> dict:
    """One layer's {name: (shape, law, std)}; ``lead`` the stacked axes."""
    d, F, Fs, E = dims.d, dims.ff, dims.shared_ff, dims.held
    if kind == "mamba":
        din, h, g, n, dx = dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state, dims.d_xbc
        mixer = {"ssd": {
            "in_proj": (lead + (d, 2 * din + 2 * g * n + h), "normal", d ** -0.5),
            "conv_w": (lead + (dims.d_conv, dx), "normal", dims.d_conv ** -0.5),
            "conv_b": (lead + (dx,), "normal", 0.1),
            "A_log": (lead + (h,), "A_log", 0.0),
            "D": (lead + (h,), "near_one", 0.1),
            "dt_bias": (lead + (h,), "dt_bias", 0.0),
            "norm_scale": (lead + (din,), "scale", 0.1),
            "out_proj": (lead + (din, d), "normal", din ** -0.5),
        }}
    else:
        H, Hkv, Dh = dims.heads, dims.kv_heads, dims.head_dim
        # Scores q.k x attention_multiplier of standard deviation near 2 on
        # unit-RMS inputs, as a trained model's are: at d ** -0.5 the 1/128
        # multiplier would leave every score near 0, and the attention a
        # plain average that no position could move.
        qk = (2.0 / (Dh ** 0.5 * dims.attn_scale)) ** 0.5 * d ** -0.5
        mixer = {"attn": {
            "wq": (lead + (d, H, Dh), "normal", qk),
            "wk": (lead + (d, Hkv, Dh), "normal", qk),
            "wv": (lead + (d, Hkv, Dh), "normal", d ** -0.5),
            "wo": (lead + (H, Dh, d), "normal", (H * Dh) ** -0.5),
        }}
    moe = {
        "router": (lead + (d, dims.experts), "normal", d ** -0.5),
        "w_gate": (lead + (E, d, F), "normal", d ** -0.5),
        "w_up": (lead + (E, d, F), "normal", d ** -0.5),
        "w_down": (lead + (E, F, d), "normal", F ** -0.5),
        "shared": {
            "w_gate": (lead + (d, Fs), "normal", d ** -0.5),
            "w_up": (lead + (d, Fs), "normal", d ** -0.5),
            "w_down": (lead + (Fs, d), "normal", Fs ** -0.5),
        },
    }
    return {"pre_norm": {"scale": (lead + (d,), "scale", 0.1)}, **mixer,
            "mlp_norm": {"scale": (lead + (d,), "scale", 0.1)}, "moe": moe}


def param_layout(dims: Dims) -> dict:
    """Nested {name: (shape, law, std)} in the program's parameter layout
    (laws: ``bench.weights``)."""
    period = dims.period
    n = dims.layers // len(period)
    # The tied readout gives the last token's own row a logit of about
    # embedding_multiplier * std^2 * d through the residual stream; the
    # layers' part spreads the logits by about std * sqrt(d) per unit of
    # their output.  At std 0.02 and d 4096 the first is 3.6 times the
    # largest of the second, and every served token repeats the prompt's
    # last one, whatever the layers compute; this std keeps it at an eighth.
    emb = 0.5 / (dims.embed_mult * dims.d ** 0.5)
    return {
        "embed": {"embedding": ((dims.vocab, dims.d), "normal", emb)},
        "blocks": [_block(dims, k, (n,)) for k in period],
        "tail": [_block(dims, k, ()) for k in dims.kinds[n * len(period):]],
        "final_norm": {"scale": ((dims.d,), "scale", 0.1)},
    }


def param_count(dims: Dims) -> int:
    """Parameters of the served model, from its shapes."""
    leaves = jax.tree.leaves(param_layout(dims), is_leaf=lambda x: isinstance(x, tuple)
                             and len(x) == 3 and isinstance(x[1], str))
    return sum(math.prod(shape) for shape, _, _ in leaves)


def pairs_per_token(dims: Dims) -> float:
    """Expected (token, held expert) pairs a token routes to, under uniform
    routing (the program counts them: ``routed_pairs``)."""
    return dims.top_k * dims.held / dims.experts


def experts_reached(dims: Dims, batch: int) -> float:
    """Expected held experts that ``batch`` tokens reach in one MoE layer,
    under uniform routing (the program counts them: ``experts_reached``)."""
    return dims.held * (1.0 - (1.0 - dims.top_k / dims.experts) ** batch)


def _mixer_linear(dims: Dims, kind: str) -> int:
    """Multiply-adds of one layer's mixer projections for one token."""
    d = dims.d
    if kind == "mamba":
        din, h, g, n = dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state
        return d * (2 * din + 2 * g * n + h) + din * d
    hd, kvd = dims.heads * dims.head_dim, dims.kv_heads * dims.head_dim
    return 2 * d * hd + 2 * d * kvd


def _expert_weights(dims: Dims) -> int:
    """Weights of one routed expert."""
    return 3 * dims.d * dims.ff


def token_flops(dims: Dims, context: int, logits: bool) -> float:
    """Operations for one token that attends to ``context`` positions
    (itself included) in the attention layers; ``logits`` adds the readout.
    The routed experts count at the expected 1.25 held pairs a token."""
    moe = (dims.d * dims.experts + 3 * dims.d * dims.shared_ff
           + pairs_per_token(dims) * _expert_weights(dims))
    total = 0.0
    for kind in dims.kinds:
        total += 2 * (_mixer_linear(dims, kind) + moe)
        if kind == "mamba":  # conv taps, state update and readout
            total += 2 * dims.d_conv * dims.d_xbc + 4 * dims.d_inner * dims.d_state
        else:  # q.k and p.v
            total += 4 * dims.heads * dims.head_dim * context
    return total + (2 * dims.d * dims.vocab if logits else 0)


def forward_flops(dims: Dims, rows: int, prompt_len: int, new_tokens: int) -> float:
    """Useful operations of one served forward (``bench.flops.forward_flops``)."""
    return flops.tokens_forward(token_flops, dims, rows, prompt_len, new_tokens)


def state_bytes(dims: Dims, batch: int, context: int) -> int:
    """Bytes of cache a decode step moves for ``batch`` rows: the attention
    layers' KV read (``context`` positions) and written (the new one); the
    Mamba-2 layers' float32 state and bf16 convolution window, each read
    and written."""
    total = 0
    for kind in dims.kinds:
        if kind == "mamba":
            ssm = batch * dims.ssd_heads * dims.headdim * dims.d_state * flops.F32
            conv = batch * (dims.d_conv - 1) * dims.d_xbc * flops.BF16
            total += 2 * (ssm + conv)
        else:
            total += batch * (context + 1) * dims.kv_heads * dims.head_dim * flops.BF16 * 2
    return total


def decode_step_cost(dims: Dims, batch: int, context: int) -> tuple[float, float]:
    """(operations, bytes) of one decode step: every bf16 weight but the
    routed experts' is read once (mixers, routers, shared experts, norms,
    the tied table), of the routed experts those the step's tokens are
    expected to reach, and the cache is read and written."""
    routed = dims.layers * dims.held * _expert_weights(dims)
    reached = dims.layers * experts_reached(dims, batch) * _expert_weights(dims)
    weights = (param_count(dims) - routed + reached) * flops.BF16
    return batch * token_flops(dims, context, logits=True), weights + state_bytes(dims, batch,
                                                                                   context)


# ------------------------------------------------------------------ reference


def _ssd(dims: Dims, quant: bool, h, s_):
    """Mamba-2 mixer output: the recurrence one position at a time."""
    b, n, _ = h.shape
    din, H, P, g, N = dims.d_inner, dims.ssd_heads, dims.headdim, dims.ngroups, dims.d_state
    zxbcdt = mm("bsd,de->bse", h, s_["in_proj"], quant, (-1,), (0,))
    xbc_end = 2 * din + 2 * g * N
    z, xbc, dt = zxbcdt[..., :din], zxbcdt[..., din:xbc_end], zxbcdt[..., xbc_end:]
    w = s_["conv_w"]  # (W, C): causal depthwise convolution, zero left context
    pad = jnp.concatenate([jnp.zeros((b, w.shape[0] - 1, xbc.shape[-1])), xbc], 1)
    xbc = jax.nn.silu(sum(pad[:, i:i + n] * w[i] for i in range(w.shape[0])) + s_["conv_b"])
    xs = xbc[..., :din].reshape(b, n, H, P)
    head_group = jnp.arange(H) // (H // g)
    Bh = xbc[..., din:din + g * N].reshape(b, n, g, N)[:, :, head_group]
    Ch = xbc[..., din + g * N:].reshape(b, n, g, N)[:, :, head_group]
    dt = jax.nn.softplus(dt + s_["dt_bias"])  # (b, n, H)
    A = -jnp.exp(s_["A_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * A)[..., None, None] * state
                 + dtt[..., None, None] * xt[..., :, None] * bt[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HI)

    seq = (xs.swapaxes(0, 1), Bh.swapaxes(0, 1), Ch.swapaxes(0, 1), dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N)), seq)
    y = (y.swapaxes(0, 1) + s_["D"][None, None, :, None] * xs).reshape(b, n, din)
    y = rms(y * jax.nn.silu(z), s_["norm_scale"], dims.eps)
    return mm("bse,ed->bsd", y, s_["out_proj"], quant, (-1,), (0,))


def _attention(dims: Dims, quant: bool, h, a):
    """Causal GQA attention output; NoPE unless the group says rope."""
    from bench.reference import rope

    q = mm("bsd,dhk->bshk", h, a["wq"], quant, (-1,), (0,))
    k = mm("bsd,dhk->bshk", h, a["wk"], quant, (-1,), (0,))
    v = mm("bsd,dhk->bshk", h, a["wv"], quant, (-1,), (0,))
    if dims.rope:
        q, k = rope(q, 10_000.0), rope(k, 10_000.0)
    group = dims.heads // dims.kv_heads  # query head i reads kv head i // group
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) * dims.attn_scale
    n = h.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    return mm("bqhk,hkd->bqd", o, a["wo"], quant, (-2, -1), (0, 1))


def _swiglu(quant: bool, h, w_gate, w_up, w_down):
    g = mm("bsd,df->bsf", h, w_gate, quant, (-1,), (0,))
    u = mm("bsd,df->bsf", h, w_up, quant, (-1,), (0,))
    return mm("bsf,fd->bsd", jax.nn.silu(g) * u, w_down, quant, (-1,), (0,))


def _moe(dims: Dims, quant: bool, h, m):
    """The held experts' share of the expert layer, plus the shared expert."""
    logits = mm("bsd,de->bse", h, m["router"], quant, (-1,), (0,))  # all experts
    top, idx = jax.lax.top_k(logits, dims.top_k)
    gates = jax.nn.softmax(top, -1)
    y = _swiglu(quant, h, m["shared"]["w_gate"], m["shared"]["w_up"], m["shared"]["w_down"])
    for e in range(dims.held):
        gate = jnp.sum(jnp.where(idx == dims.offset + e, gates, 0.0), -1)  # (b, s)
        y = y + gate[..., None] * _swiglu(quant, h, m["w_gate"][e], m["w_up"][e], m["w_down"][e])
    return y


def _layer(dims: Dims, quant: bool, kind: str, x, p):
    h = rms(x, p["pre_norm"]["scale"], dims.eps)
    y = _ssd(dims, quant, h, p["ssd"]) if kind == "mamba" else _attention(dims, quant, h, p["attn"])
    x = x + dims.residual_mult * y
    h = rms(x, p["mlp_norm"]["scale"], dims.eps)
    return x + dims.residual_mult * _moe(dims, quant, h, p["moe"])


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(dims: Dims, params, tokens, quant: bool = False):
    """(B, S, V) float32 reference logits of ``tokens`` (B, S) at every
    position: the layers in order over the whole sequence, no cache."""
    period = dims.period
    n = dims.layers // len(period)

    def one_period(x, ps):
        for kind, p in zip(period, ps):
            x = _layer(dims, quant, kind, x, p)
        return x

    def blocks(x):
        x = scan_layers(one_period, params["blocks"], x * dims.embed_mult)
        for kind, p in zip(dims.kinds[n * len(period):], params["tail"]):
            x = _layer(dims, quant, kind, x, jax.tree.map(lambda a: a.astype(jnp.float32), p))
        return x

    return tied_logits(params, tokens, quant, dims.eps, blocks) / dims.logits_div
