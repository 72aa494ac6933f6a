"""Model FLOP utilization of the served forwards (%).

Useful operations of every forward (its unpadded rows: the prompt and the
decoded tokens, ``bench/flops.py``) over the forwards' measured seconds
times the chip's bf16 peak.
"""
from bench import flops


def read(rec: dict):
    """Percent, or None when no forward ran."""
    f = rec["forwards"]
    secs = sum(x["prefill_s"] + x["decode_s"] for x in f)
    if not f or secs <= 0:
        return None
    ops = sum(flops.forward_flops(rec["dims_by_model"][x["model"]], x["rows"],
                                  rec["prompt_len"], rec["new_tokens"]) for x in f)
    return 100.0 * ops / (secs * rec["peaks"]["bf16_flops_per_s"])
