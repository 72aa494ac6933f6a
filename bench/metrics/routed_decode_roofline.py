"""Roofline share of the accurate model's decode steps on the device (%).

The least time of a step is the larger of its operations over the chip's
bf16 peak and its bytes over HBM bandwidth, by the architecture's
``decode_step_cost`` at the padded batch the step ran (for a held-expert
MoE model: the weights outside the routed experts, the routed experts the
step's tokens are expected to reach, and the KV and SSM state).  The share
is that least time, summed over the decode steps of the accurate model's
profiled forwards, over the device-busy time of the decode program inside
those ``exec.forward`` spans (``bench/spans.py``).
"""
from bench import flops


def read(rec: dict):
    """Percent, or None without profiled decode steps of the accurate model."""
    name = rec["roles"]["accurate"].name
    dims = rec["dims_by_model"][name]
    fwd = [f for f in (rec.get("spans") or {}).get("forwards", [])
           if f["model"] == name and f["decode_dev_s"] and f.get("padded")]
    if not fwd or rec["new_tokens"] < 2:
        return None
    least = 0.0
    for f in fwd:
        for j in range(rec["new_tokens"] - 1):
            ops, nbytes = flops.decode_step_cost(dims, int(f["padded"]), rec["prompt_len"] + j + 1)
            least += flops.least_time(ops, nbytes, rec["peaks"])[0]
    return 100.0 * least / sum(f["decode_dev_s"] for f in fwd)
