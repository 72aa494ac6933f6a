"""Share of the traced window in which no operation ran on the device (%)."""


def read(rec: dict):
    """Percent, or None without a trace."""
    t = rec.get("trace")
    if not t or t.get("idle_share") is None:
        return None
    return 100.0 * t["idle_share"]
