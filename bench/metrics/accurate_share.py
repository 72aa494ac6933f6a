"""Share of the window's served requests assigned to the accurate model (%)."""


def read(rec: dict):
    """Percent of measured served requests, or None."""
    reqs = rec["requests"]
    if not reqs:
        return None
    acc = rec["roles"]["accurate"].name
    return 100.0 * sum(r["model"] == acc for r in reqs) / len(reqs)
