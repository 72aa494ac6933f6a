"""Host time of the scheduler's selection per window (ms): the median over
the profiled windows of the program's ``serve.select`` span."""
from bench import spans


def read(rec: dict):
    """Median per profiled window, or None without program spans."""
    return spans.median_ms(rec.get("spans"), "select_s")
