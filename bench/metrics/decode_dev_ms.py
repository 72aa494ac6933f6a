"""Device-busy time of the decode program per served forward (ms): its
module events (``jit_decode_step``) inside each ``exec.forward`` span,
mean over the profiled forwards (``bench/spans.py``)."""


def read(rec: dict):
    """Mean per profiled forward, or None without program spans."""
    f = [x["decode_dev_s"] for x in (rec.get("spans") or {}).get("forwards", [])
         if x["decode_dev_s"] is not None]
    return 1e3 * sum(f) / len(f) if f else None
