"""Mamba-2: SSD mixer blocks with no MLP (mamba2-130m).

A group holds its published config's keys: ``d_model``, ``n_layer``,
``d_state``, ``headdim``, ``expand``, ``ngroups``, ``d_conv``,
``chunk_size``, ``rms_norm_eps``, ``vocab_size`` and ``model_name``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import HI, mm, rms, scan_layers, tied_logits

# Widest normalized gap allowed for a served token.  Set from TPU v5e
# readings over a dozen seeds and more: the program at most 0.2006, the
# float8 control at least 0.819 (PERF.md).
GAP_LIMIT = 0.5


@dataclasses.dataclass(frozen=True)
class Dims:
    """Shapes of one served Mamba-2 model."""

    name: str
    layers: int
    d: int
    vocab: int
    d_state: int
    headdim: int
    expand: int
    ngroups: int
    d_conv: int
    chunk: int
    eps: float = 1e-6

    @property
    def d_inner(self) -> int:
        return self.expand * self.d

    @property
    def ssd_heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def d_xbc(self) -> int:
        return self.d_inner + 2 * self.ngroups * self.d_state


def dims(c: dict) -> Dims:
    """``Dims`` of one model group of a configuration file."""
    return Dims(
        name=c["model_name"], layers=c["n_layer"], d=c["d_model"], vocab=c["vocab_size"],
        d_state=c["d_state"], headdim=c["headdim"], expand=c["expand"],
        ngroups=c["ngroups"], d_conv=c["d_conv"], chunk=c["chunk_size"],
        eps=float(c["rms_norm_eps"]),
    )


def model_config(dims: Dims):
    """The program's ``ModelConfig`` for these sizes (bf16, as served)."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=dims.name, family="ssm", num_layers=dims.layers, d_model=dims.d,
        vocab_size=dims.vocab, pattern=("ssd:none",), ssd_state=dims.d_state,
        ssd_headdim=dims.headdim, ssd_expand=dims.expand, ssd_ngroups=dims.ngroups,
        ssd_chunk=dims.chunk, conv_width=dims.d_conv, tie_embeddings=True,
    )


def param_layout(dims: Dims) -> dict:
    """Nested {name: (shape, law, std)} in the program's parameter layout
    (laws: ``bench.weights``)."""
    L, d = dims.layers, dims.d
    din, h, g, n, dx = dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state, dims.d_xbc
    block = {
        "pre_norm": {"scale": ((L, d), "scale", 0.1)},
        "ssd": {
            "in_proj": ((L, d, 2 * din + 2 * g * n + h), "normal", d ** -0.5),
            "conv_w": ((L, dims.d_conv, dx), "normal", dims.d_conv ** -0.5),
            "conv_b": ((L, dx), "normal", 0.1),
            "A_log": ((L, h), "A_log", 0.0),
            "D": ((L, h), "near_one", 0.1),
            "dt_bias": ((L, h), "dt_bias", 0.0),
            "norm_scale": ((L, din), "scale", 0.1),
            "out_proj": ((L, din, d), "normal", din ** -0.5),
        },
    }
    return {
        "embed": {"embedding": ((dims.vocab, d), "normal", 0.02)},
        "blocks": [block],
        "tail": [],
        "final_norm": {"scale": ((d,), "scale", 0.1)},
    }


def _layer_linear(dims: Dims) -> int:
    """Multiply-adds of one layer's linear maps for one token."""
    d, din, h, g, n = dims.d, dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state
    return d * (2 * din + 2 * g * n + h) + din * d


def param_count(dims: Dims) -> int:
    """Parameters of the served model, from its shapes."""
    d, din, h, dx = dims.d, dims.d_inner, dims.ssd_heads, dims.d_xbc
    layer = d + _layer_linear(dims) + dims.d_conv * dx + dx + 3 * h + din
    return dims.vocab * d + d + dims.layers * layer


def token_flops(dims: Dims, context: int, logits: bool) -> int:
    """Operations for one token (``context`` changes nothing: the state is
    fixed-size); ``logits`` adds the readout over the vocabulary."""
    # linear maps, depthwise conv taps, then state update and readout per (head, p, n)
    per_layer = (2 * _layer_linear(dims) + 2 * dims.d_conv * dims.d_xbc
                 + 4 * dims.d_inner * dims.d_state)
    return dims.layers * per_layer + (2 * dims.d * dims.vocab if logits else 0)


def forward_flops(dims: Dims, rows: int, prompt_len: int, new_tokens: int) -> int:
    """Useful operations of one served forward (``bench.flops.forward_flops``)."""
    return flops.tokens_forward(token_flops, dims, rows, prompt_len, new_tokens)


def state_bytes(dims: Dims, batch: int, context: int) -> int:
    """Bytes of SSM state and convolution window a decode step reads and
    writes for ``batch`` rows."""
    ssm = batch * dims.ssd_heads * dims.headdim * dims.d_state * flops.F32
    conv = batch * (dims.d_conv - 1) * dims.d_xbc * flops.BF16
    return dims.layers * 2 * (ssm + conv)  # read and write


def decode_step_cost(dims: Dims, batch: int, context: int) -> tuple[int, int]:
    """(operations, bytes) of one decode step: every bf16 weight is read
    once, and the state is read and written."""
    ops = batch * token_flops(dims, context, logits=True)
    return ops, param_count(dims) * flops.BF16 + state_bytes(dims, batch, context)


def _layer(dims: Dims, quant: bool, x, p):
    s_ = p["ssd"]
    b, n, _ = x.shape
    din, H, P, g, N = dims.d_inner, dims.ssd_heads, dims.headdim, dims.ngroups, dims.d_state
    h = rms(x, p["pre_norm"]["scale"], dims.eps)
    zxbcdt = mm("bsd,de->bse", h, s_["in_proj"], quant, (-1,), (0,))
    z, xbc, dt = zxbcdt[..., :din], zxbcdt[..., din:2 * din + 2 * g * N], zxbcdt[..., 2 * din + 2 * g * N:]
    w = s_["conv_w"]  # (W, C): causal depthwise convolution, zero left context
    pad = jnp.concatenate([jnp.zeros((b, w.shape[0] - 1, xbc.shape[-1])), xbc], 1)
    conv = sum(pad[:, i:i + n] * w[i] for i in range(w.shape[0])) + s_["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :din].reshape(b, n, H, P)
    Bm = xbc[..., din:din + g * N].reshape(b, n, g, N)
    Cm = xbc[..., din + g * N:].reshape(b, n, g, N)
    head_group = jnp.arange(H) // (H // g)
    Bh, Ch = Bm[:, :, head_group], Cm[:, :, head_group]  # (b, n, H, N)
    dt = jax.nn.softplus(dt + s_["dt_bias"])  # (b, n, H)
    A = -jnp.exp(s_["A_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = jnp.exp(dtt * A)[..., None, None] * state + (dtt[..., None, None]
                                                             * xt[..., :, None] * bt[..., None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct, precision=HI)

    seq = (xs.swapaxes(0, 1), Bh.swapaxes(0, 1), Ch.swapaxes(0, 1), dt.swapaxes(0, 1))
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N)), seq)
    y = y.swapaxes(0, 1) + s_["D"][None, None, :, None] * xs
    y = y.reshape(b, n, din) * jax.nn.silu(z)
    y = rms(y, s_["norm_scale"], dims.eps)
    return x + mm("bse,ed->bsd", y, s_["out_proj"], quant, (-1,), (0,))


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(dims: Dims, params, tokens, quant: bool = False):
    """(B, S, V) float32 reference logits of ``tokens`` (B, S) at every
    position: the recurrence one position at a time, from a zero state."""
    layer = functools.partial(_layer, dims, quant)
    return tied_logits(params, tokens, quant, dims.eps,
                       lambda x: scan_layers(layer, params["blocks"][0], x))
