"""Plain reference of the served LMs: float32 ``jax.numpy`` at highest precision.

Written from the published descriptions, with the departures that the
configuration files list (RoPE and RMSNorm in every transformer, tanh GELU,
tied embeddings, a single codebook).  No kernels, no cache, no batching
tricks: the transformer attends over the whole sequence with an explicit
causal mask, and Mamba-2 runs its recurrence one position at a time.  It
reads the weights the benchmark made (``bench.weights``) in their bf16 type
and computes in float32, one layer at a time under ``lax.scan``.

``quant=True`` is the control: the inputs of every linear layer, of the
embedding and of the logits rounded to float8 (e4m3, scaled per output
channel for weights and per token for activations), the step below the
bf16 that the configurations serve.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models import Dims

HI = jax.lax.Precision.HIGHEST


F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x, axes):
    """float8 (e4m3) round trip, scaled by the absolute maximum over ``axes``
    (the contracted ones)."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, x, w, quant, x_axes, w_axes):
    if quant:
        x, w = _q8(x, x_axes), _q8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def _rope(x, theta):
    """Rotary embedding, rotate-half form.  x: (B, S, H, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, Dh/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attn_layer(dims: Dims, quant: bool, x, p):
    a = p["attn"]
    h = _rms(x, p["pre_norm"]["scale"], dims.eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"], quant, (-1,), (0,))
    k = _mm("bsd,dhk->bshk", h, a["wk"], quant, (-1,), (0,))
    v = _mm("bsd,dhk->bshk", h, a["wv"], quant, (-1,), (0,))
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    group = dims.heads // dims.kv_heads  # query head i reads kv head i // group
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / jnp.sqrt(float(dims.head_dim))
    n = x.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + _mm("bqhk,hkd->bqd", o, a["wo"], quant, (-2, -1), (0, 1))
    h = _rms(x, p["mlp_norm"]["scale"], dims.eps)
    m = p["mlp"]
    up = _mm("bsd,df->bsf", h, m["w_up"], quant, (-1,), (0,))
    if dims.gated:
        up = jax.nn.silu(_mm("bsd,df->bsf", h, m["w_gate"], quant, (-1,), (0,))) * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    return x + _mm("bsf,fd->bsd", up, m["w_down"], quant, (-1,), (0,))


def _ssd_layer(dims: Dims, quant: bool, x, p):
    s_ = p["ssd"]
    b, n, _ = x.shape
    din, H, P, g, N = dims.d_inner, dims.ssd_heads, dims.headdim, dims.ngroups, dims.d_state
    h = _rms(x, p["pre_norm"]["scale"], dims.eps)
    zxbcdt = _mm("bsd,de->bse", h, s_["in_proj"], quant, (-1,), (0,))
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
    y = _rms(y, s_["norm_scale"], dims.eps)
    return x + _mm("bse,ed->bsd", y, s_["out_proj"], quant, (-1,), (0,))


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(dims: Dims, params, tokens, quant: bool = False):
    """(B, S, V) float32 logits of ``tokens`` (B, S) int32 at every position."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    table = params["embed"]["embedding"].astype(jnp.float32)
    if quant:
        table = _q8(table, (-1,))
    x = table[tokens]
    layer = _ssd_layer if dims.kind == "ssd" else _attn_layer

    def body(x, p):
        return layer(dims, quant, x, f32(p)), None

    x, _ = jax.lax.scan(body, x, params["blocks"][0])
    x = _rms(x, params["final_norm"]["scale"].astype(jnp.float32), dims.eps)
    return _mm("bsd,vd->bsv", x, table, quant, (-1,), (-1,))
