"""Host time of a window's dispatch outside the forwards' prefill and decode
(ms): the median over the profiled windows of ``serve.dispatch`` less the
``exec.prefill`` and ``exec.decode`` spans inside it (padding, merging,
token readback, the report split)."""
from bench import spans


def read(rec: dict):
    """Median per profiled window, or None without program spans."""
    return spans.median_ms(rec.get("spans"), "dispatch_host_s")
