"""Host time of the SneakPeek models' ``evidence_batch`` per window (ms)."""


def read(rec: dict):
    """Mean per measured window, or None."""
    w = rec["windows"]
    return 1e3 * sum(x["ingest_s"] for x in w) / len(w) if w else None
