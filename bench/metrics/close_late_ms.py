"""Mean lateness of a window close after its tick, on the benchmark's clock (ms)."""


def read(rec: dict):
    """Mean of the measured windows' close lateness, or None."""
    w = rec["windows"]
    return 1e3 * sum(x["late_s"] for x in w) / len(w) if w else None
