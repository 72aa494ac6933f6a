"""Operations and bytes of the served LMs, counted from their shapes.

A multiply-add counts two operations.  Only the work the model's equations
need is counted: the linear layers, attention scores and values over the
positions a token attends to, the Mamba-2 convolution and state update,
and the logits of the positions whose logits are read (the last prompt
position and each decoded token).  What depends on the architecture lives
in its module (``bench/archs/``); the readers call the two functions here.
"""
from __future__ import annotations

from bench.models import arch

BF16 = 2
F32 = 4


def tokens_forward(token_flops, dims, rows: int, prompt_len: int, new_tokens: int) -> int:
    """Sum of ``token_flops(dims, context, logits)`` over one served forward
    of ``rows`` requests: the prompt (logits at its last position) and
    ``new_tokens - 1`` decode steps (the first new token comes from the
    prompt's logits)."""
    prompt = sum(token_flops(dims, i + 1, logits=(i == prompt_len - 1))
                 for i in range(prompt_len))
    decode = sum(token_flops(dims, prompt_len + j + 1, logits=True)
                 for j in range(new_tokens - 1))
    return rows * (prompt + decode)


def forward_flops(dims, rows: int, prompt_len: int, new_tokens: int) -> int:
    """Useful operations of one served forward over ``rows`` requests of
    ``prompt_len`` prompt tokens and ``new_tokens`` new ones."""
    return arch(dims).forward_flops(dims, rows, prompt_len, new_tokens)


def decode_step_cost(dims, batch: int, context: int) -> tuple[int, int]:
    """(operations, bytes) of one decode step for ``batch`` rows whose new
    token attends to ``context`` positions."""
    return arch(dims).decode_step_cost(dims, batch, context)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) of the roofline: the larger of compute and memory time."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
