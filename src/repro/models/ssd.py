"""Mamba-2 SSD (state-space duality) mixer: chunked prefill + O(1) decode.

Follows the minimal SSD algorithm of [arXiv:2405.21060] §6: the sequence
is split into chunks; within-chunk outputs use the quadratic "attention
form" with the causal decay matrix L = exp(segsum(dt*A)); chunk states
are passed through a (sequential, cheap) inter-chunk recurrence.

Layout: x (B, S, H, P) heads x headdim; B/C (B, S, G, N) state
projections shared across H/G head groups; A scalar per head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.spec import P

__all__ = ["ssd_spec", "ssd_forward", "ssd_decode_step", "ssd_init_cache_shapes", "segsum"]


def ssd_spec(cfg) -> dict:
    d, din = cfg.d_model, cfg.d_inner
    g, n, h = cfg.ssd_ngroups, cfg.ssd_state, cfg.ssd_heads
    d_xbc = din + 2 * g * n
    return {
        "in_proj": P((d, 2 * din + 2 * g * n + h), ("embed", "ssd_inner")),
        "conv_w": P((cfg.conv_width, d_xbc), ("conv", "ssd_inner"), init="small"),
        "conv_b": P((d_xbc,), ("ssd_inner",), init="zeros"),
        "A_log": P((h,), ("ssd_heads",), init="zeros"),  # A = -exp(A_log) => -1 at init
        "D": P((h,), ("ssd_heads",), init="ones"),
        "dt_bias": P((h,), ("ssd_heads",), init="zeros"),
        "norm_scale": P((din,), ("ssd_inner",), init="zeros"),
        "out_proj": P((din, d), ("ssd_inner", "embed")),
    }


def segsum(x):
    """x: (..., L) -> (..., L, L);  out[i, j] = sum_{k=j+1..i} x_k for i >= j,
    -inf above the diagonal (so exp(.) is the causal decay-product matrix)."""
    L = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, d, -jnp.inf)


def _causal_conv(x, w, b, state=None):
    """Depthwise causal 1-D conv.  x: (B, S, C); w: (W, C).

    ``state`` (B, W-1, C) provides left context (decode/chunk carry);
    zeros otherwise.  Returns (y, new_state)."""
    bsz, s, c = x.shape
    wlen = w.shape[0]
    if state is None:
        state = jnp.zeros((bsz, wlen - 1, c), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, W-1+S, C)
    y = jnp.zeros((bsz, s, c), jnp.float32)
    for i in range(wlen):  # W is tiny (4): unrolled taps
        y = y + xp[:, i : i + s, :].astype(jnp.float32) * w[i].astype(jnp.float32)
    y = y + b.astype(jnp.float32)
    new_state = xp[:, s:, :] if s >= wlen - 1 else xp[:, -(wlen - 1):, :]
    return y.astype(x.dtype), new_state


def _gated_rmsnorm(scale, x, z, eps=1e-6):
    """Mamba-2 norm: RMSNorm(x * silu(z)) with (1+scale)."""
    x = x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def _split_zxbcdt(cfg, zxbcdt):
    din, g, n, h = cfg.d_inner, cfg.ssd_ngroups, cfg.ssd_state, cfg.ssd_heads
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din : 2 * din + 2 * g * n]
    dt = zxbcdt[..., 2 * din + 2 * g * n :]
    return z, xbc, dt


def ssd_scan(x, dt, a_per_head, B, C, chunk):
    """Core chunked SSD.  x: (b,s,h,p); dt: (b,s,h) (post-softplus);
    a_per_head: (h,) negative; B, C: (b,s,g,n).  Returns (y, final_state)
    with final_state (b, h, p, n)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    hg = h // g
    s_orig = s
    if s % chunk:
        # Pad with dt = 0 steps: decay exp(0) = 1 and zero input
        # contribution, so the recurrence (and final state) are unchanged.
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s += pad
    nc = s // chunk

    dA = dt * a_per_head[None, None, :]  # (b, s, h)  negative decays
    xdt = x * dt[..., None]  # (b, s, h, p)

    # chunked views
    dAc = dA.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2)  # (b, h, nc, l)
    xc = xdt.reshape(b, nc, chunk, h, p)
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)
    xcg = xc.reshape(b, nc, chunk, g, hg, p)

    # ---- intra-chunk (attention form)
    L = jnp.exp(segsum(dAc))  # (b, h, nc, l, l)
    Lg = L.reshape(b, g, hg, nc, chunk, chunk)
    scores = jnp.einsum("bclgn,bcsgn->bgcls", Cc, Bc, preferred_element_type=jnp.float32)
    y_diag = jnp.einsum(
        "bgcls,bghcls,bcsghp->bclghp",
        scores.astype(x.dtype),
        Lg.astype(x.dtype),
        xcg,
        preferred_element_type=jnp.float32,
    )

    # ---- chunk states
    cum = jnp.cumsum(dAc, axis=-1)  # (b, h, nc, l)
    decay_to_end = jnp.exp(cum[..., -1:] - cum)  # (b, h, nc, l)
    dg = decay_to_end.reshape(b, g, hg, nc, chunk)
    states = jnp.einsum(
        "bcsgn,bghcs,bcsghp->bcghpn", Bc, dg.astype(x.dtype), xcg,
        preferred_element_type=jnp.float32,
    )  # (b, nc, g, hg, p, n)

    # ---- inter-chunk recurrence over chunk states
    chunk_decay = jnp.exp(cum[..., -1])  # (b, h, nc)
    cd = chunk_decay.reshape(b, g, hg, nc).transpose(3, 0, 1, 2)  # (nc, b, g, hg)
    st = states.transpose(1, 0, 2, 3, 4, 5)  # (nc, b, g, hg, p, n)

    def step(carry, inp):
        s_prev = carry
        decay, s_new = inp
        out = s_prev  # state BEFORE this chunk
        carry = decay[..., None, None] * s_prev + s_new
        return carry, out

    init = jnp.zeros((b, g, hg, x.shape[3], n), jnp.float32)
    final_state, prev_states = jax.lax.scan(step, init, (cd.astype(jnp.float32), st))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4, 5)  # (b, nc, g, hg, p, n)

    # ---- inter-chunk output
    decay_out = jnp.exp(cum).reshape(b, g, hg, nc, chunk)  # decay from chunk start
    y_off = jnp.einsum(
        "bclgn,bcghpn,bghcl->bclghp",
        Cc,
        prev_states.astype(x.dtype),
        decay_out.astype(x.dtype),
        preferred_element_type=jnp.float32,
    )

    y = (y_diag + y_off).reshape(b, nc, chunk, h, p).reshape(b, s, h, p)
    return y[:, :s_orig].astype(x.dtype), final_state.reshape(b, h, x.shape[3], n)


def ssd_forward(params, x, cfg, conv_state=None, ssm_state_in=None):
    """Full-sequence SSD mixer.  x: (B, S, D).

    Returns (y, (conv_state, ssm_state)) — the cache needed to continue
    decoding after prefill."""
    b, s, d = x.shape
    h, p = cfg.ssd_heads, cfg.ssd_headdim
    g, n = cfg.ssd_ngroups, cfg.ssd_state
    din = cfg.d_inner

    zxbcdt = x @ params["in_proj"]
    z, xbc, dt = _split_zxbcdt(cfg, zxbcdt)
    xbc, conv_state = _causal_conv(xbc, params["conv_w"], params["conv_b"], conv_state)
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)
    xin = xbc[..., :din].reshape(b, s, h, p)
    Bmat = xbc[..., din : din + g * n].reshape(b, s, g, n)
    Cmat = xbc[..., din + g * n :].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["A_log"].astype(jnp.float32))

    xin = shard_act(xin, "ssd_x")
    y, ssm_state = ssd_scan(xin, dt.astype(jnp.float32), a, Bmat, Cmat, cfg.ssd_chunk)
    if ssm_state_in is not None:
        # Carried prefix state is rare in this framework (prefill always
        # starts at 0); supported for chunked prefill continuation.
        raise NotImplementedError("prefix ssm state continuation not supported")
    y = y + params["D"].astype(x.dtype)[None, None, :, None] * xin
    y = y.reshape(b, s, din)
    y = _gated_rmsnorm(params["norm_scale"], y, z, cfg.norm_eps)
    return y @ params["out_proj"], (conv_state, ssm_state.astype(jnp.float32))


def ssd_decode_step(params, x, cache, cfg):
    """One-token SSD step.  x: (B, 1, D); cache = (conv_state, ssm_state)."""
    conv_state, ssm_state = cache
    b = x.shape[0]
    h, p = cfg.ssd_heads, cfg.ssd_headdim
    g, n = cfg.ssd_ngroups, cfg.ssd_state
    din = cfg.d_inner

    zxbcdt = x @ params["in_proj"]  # (B, 1, ...)
    z, xbc, dt = _split_zxbcdt(cfg, zxbcdt)
    xbc, conv_state = _causal_conv(xbc, params["conv_w"], params["conv_b"], conv_state)
    xbc = jax.nn.silu(xbc.astype(jnp.float32)).astype(x.dtype)
    xin = xbc[..., :din].reshape(b, h, p)
    Bv = xbc[..., din : din + g * n].reshape(b, g, n)
    Cv = xbc[..., din + g * n :].reshape(b, g, n)
    dt1 = jax.nn.softplus(dt.astype(jnp.float32)[:, 0] + params["dt_bias"])  # (B, h)
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt1 * a[None, :])  # (B, h)

    hg = h // g
    xg = xin.reshape(b, g, hg, p)
    dtg = dt1.reshape(b, g, hg)
    # state update: S <- decay * S + dt * B (outer) x
    upd = jnp.einsum("bgn,bghp,bgh->bghpn", Bv, xg.astype(jnp.float32), dtg)
    ssm_state = decay.reshape(b, g, hg)[..., None, None].astype(jnp.float32) * ssm_state.reshape(
        b, g, hg, p, n
    ) + upd
    y = jnp.einsum("bgn,bghpn->bghp", Cv.astype(jnp.float32), ssm_state)
    ssm_state = ssm_state.reshape(b, h, p, n)
    y = y.reshape(b, h, p) + params["D"].astype(jnp.float32)[None, :, None] * xin.astype(jnp.float32)
    y = y.reshape(b, 1, din).astype(x.dtype)
    y = _gated_rmsnorm(params["norm_scale"], y, z, cfg.norm_eps)
    return y @ params["out_proj"], (conv_state, ssm_state)


def ssd_init_cache_shapes(cfg, batch: int):
    """(conv_state, ssm_state) shapes for cache allocation."""
    d_xbc = cfg.d_inner + 2 * cfg.ssd_ngroups * cfg.ssd_state
    return (
        (batch, cfg.conv_width - 1, d_xbc),
        (batch, cfg.ssd_heads, cfg.ssd_headdim, cfg.ssd_state),
    )
