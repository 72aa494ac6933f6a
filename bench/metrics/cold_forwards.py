"""Forwards in the measured window that ran a shape the backend had not run
before (``ServeStats.cold_forwards``' growth): each one compiled there."""


def read(rec: dict):
    """Count, or None where the program has no such counter."""
    return rec.get("cold_forwards")
