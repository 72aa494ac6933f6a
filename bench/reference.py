"""Plain reference of the served LMs: float32 ``jax.numpy`` at highest precision.

Written from the published descriptions, with the departures that the
configuration files list (RoPE and RMSNorm in every transformer, tanh GELU,
tied embeddings, a single codebook).  No kernels, no cache, no batching
tricks.  Each architecture's layers live in its module (``bench/archs/``);
this module holds what they share: the float8 round trip, the linear map,
RMSNorm, RoPE, the tied embedding and readout, and the scan over stacked
layers.  A reference reads the weights the benchmark made (``bench.weights``)
in their bf16 type and computes in float32, one layer at a time under
``lax.scan``.

``quant=True`` is the control: the inputs of every linear layer, of the
embedding and of the logits rounded to float8 (e4m3, scaled per output
channel for weights and per token for activations), the step below the
bf16 that the configurations serve.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models import arch

HI = jax.lax.Precision.HIGHEST


F8_MAX = 448.0  # largest finite float8_e4m3fn


def q8(x, axes):
    """float8 (e4m3) round trip, scaled by the absolute maximum over ``axes``
    (the contracted ones)."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec, x, w, quant, x_axes, w_axes):
    """``einsum(spec, x, w)`` at highest precision, both sides through
    ``q8`` over their contracted axes under the control."""
    if quant:
        x, w = q8(x, x_axes), q8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def rms(x, scale, eps):
    """RMSNorm in the program's (1 + scale) form."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + scale)


def rope(x, theta):
    """Rotary embedding, rotate-half form.  x: (B, S, H, Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, Dh/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def scan_layers(layer, stacked, x):
    """``layer(x, p)`` over the stacked layers ``stacked``, each cast to float32."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))

    def body(x, p):
        return layer(x, f32(p)), None

    return jax.lax.scan(body, x, stacked)[0]


def tied_logits(params, tokens, quant: bool, eps: float, blocks):
    """Logits of a tied-embedding LM: embed ``tokens``, ``blocks(x)``, the
    final RMSNorm, and the readout through the embedding table."""
    table = params["embed"]["embedding"].astype(jnp.float32)
    if quant:
        table = q8(table, (-1,))
    x = blocks(table[tokens])
    x = rms(x, params["final_norm"]["scale"].astype(jnp.float32), eps)
    return mm("bsd,vd->bsv", x, table, quant, (-1,), (-1,))


def logits(dims, params, tokens, quant: bool = False):
    """(B, S, V) float32 logits of ``tokens`` (B, S) int32 at every position,
    by the reference of ``dims``' architecture."""
    return arch(dims).logits(dims, params, tokens, quant)
