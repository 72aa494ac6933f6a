"""Operations and bytes of the served LMs, counted from their shapes.

A multiply-add counts two operations.  Only the work the model's equations
need is counted: the linear layers, attention scores and values over the
positions a token attends to, the Mamba-2 convolution and state update,
and the logits of the positions whose logits are read (the last prompt
position and each decoded token).
"""
from __future__ import annotations

from bench.models import Dims, param_count

BF16 = 2
F32 = 4


def _layer_linear(dims: Dims) -> int:
    """Multiply-adds of one layer's linear maps for one token."""
    d = dims.d
    if dims.kind == "ssd":
        din, h, g, n = dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state
        return d * (2 * din + 2 * g * n + h) + din * d
    H, Hkv, Dh, F = dims.heads, dims.kv_heads, dims.head_dim, dims.ff
    return d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d + (3 if dims.gated else 2) * d * F


def token_flops(dims: Dims, context: int, logits: bool) -> int:
    """Operations for one token that attends to ``context`` positions
    (itself included); ``logits`` adds the readout over the vocabulary."""
    per_layer = 2 * _layer_linear(dims)
    if dims.kind == "ssd":
        # depthwise conv taps, then state update and readout per (head, p, n)
        per_layer += 2 * dims.d_conv * dims.d_xbc + 4 * dims.d_inner * dims.d_state
    else:
        per_layer += 4 * dims.heads * dims.head_dim * context  # q.k and p.v
    total = dims.layers * per_layer
    if logits:
        total += 2 * dims.d * dims.vocab
    return total


def forward_flops(dims: Dims, rows: int, prompt_len: int, new_tokens: int) -> int:
    """Useful operations of one served forward over ``rows`` requests:
    the prompt (logits at its last position) and ``new_tokens - 1`` decode
    steps (the first new token comes from the prompt's logits)."""
    prompt = sum(token_flops(dims, i + 1, logits=(i == prompt_len - 1))
                 for i in range(prompt_len))
    decode = sum(token_flops(dims, prompt_len + j + 1, logits=True)
                 for j in range(new_tokens - 1))
    return rows * (prompt + decode)


def state_bytes(dims: Dims, batch: int, context: int) -> int:
    """Bytes of cache a decode step must read and write for ``batch`` rows."""
    if dims.kind == "ssd":
        ssm = batch * dims.ssd_heads * dims.headdim * dims.d_state * F32
        conv = batch * (dims.d_conv - 1) * dims.d_xbc * BF16
        return dims.layers * 2 * (ssm + conv)  # read and write
    kv = batch * context * dims.kv_heads * dims.head_dim * BF16 * 2  # keys and values
    new = batch * dims.kv_heads * dims.head_dim * BF16 * 2
    return dims.layers * (kv + new)


def decode_step_cost(dims: Dims, batch: int, context: int) -> tuple[int, int]:
    """(operations, bytes) of one decode step for ``batch`` rows whose new
    token attends to ``context`` positions: every bf16 weight is read once,
    and the cache is read (and the new entry written)."""
    flops = batch * token_flops(dims, context, logits=True)
    nbytes = param_count(dims) * BF16 + state_bytes(dims, batch, context)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of compute and memory time."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
