"""Scheduler host time per window (ms): the window's growth of
``ServeStats.sched_wall_s`` less its ingest time."""


def read(rec: dict):
    """Mean per measured window, or None."""
    w = rec["windows"]
    return 1e3 * sum(x["sched_s"] - x["ingest_s"] for x in w) / len(w) if w else None
