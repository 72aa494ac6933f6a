"""Model FLOP utilization of the accurate model's forwards (%).

Useful operations of each of its forwards (the unpadded rows: the prompt
and the decoded tokens, ``bench/flops.py``; for a held-expert MoE model the
routed experts at the expected pairs a token), over those forwards'
measured seconds times the chip's bf16 peak.
"""
from bench import flops


def read(rec: dict):
    """Percent, or None when the accurate model ran no forward."""
    name = rec["roles"]["accurate"].name
    f = [x for x in rec["forwards"] if x["model"] == name]
    secs = sum(x["prefill_s"] + x["decode_s"] for x in f)
    if not f or secs <= 0:
        return None
    ops = sum(flops.forward_flops(rec["dims_by_model"][name], x["rows"], rec["prompt_len"],
                                  rec["new_tokens"]) for x in f)
    return 100.0 * ops / (secs * rec["peaks"]["bf16_flops_per_s"])
