"""Device-idle time inside a window close, from the program's spans (ms):
the median over the profiled windows of the time inside each
``serve.window`` span in which no op ran on the device (``bench/spans.py``)."""
from bench import spans


def read(rec: dict):
    """Median per profiled window, or None without program spans."""
    return spans.median_ms(rec.get("spans"), "idle_s")
