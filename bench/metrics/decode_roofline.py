"""Roofline share of the decode steps (%).

The least time of a step is the larger of its operations over the chip's
bf16 peak and its bytes (every bf16 weight, plus the KV or SSM state) over
HBM bandwidth (``bench/flops.py``), at the padded batch the step ran; the
share is the least time of all decode steps over their measured time.
"""
from bench import flops


def least_decode_s(rec: dict, fwd: dict) -> tuple[float, str]:
    """(least seconds, bound of the first step) of one forward's decode steps."""
    dims = rec["dims_by_model"][fwd["model"]]
    total, bound = 0.0, None
    for j in range(rec["new_tokens"] - 1):
        f, b = flops.decode_step_cost(dims, fwd["padded"], fwd["seq"] + j + 1)
        t, bd = flops.least_time(f, b, rec["peaks"])
        total += t
        bound = bound or bd
    return total, bound


def read(rec: dict):
    """Percent, or None when no decode step ran."""
    f = [x for x in rec["forwards"] if x["decode_s"] > 0]
    if not f or rec["new_tokens"] < 2:
        return None
    least = sum(least_decode_s(rec, x)[0] for x in f)
    return 100.0 * least / sum(x["decode_s"] for x in f)
