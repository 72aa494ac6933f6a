"""``decode_s`` per served forward (all its decode steps), in ms."""


def read(rec: dict):
    """Mean over the window's forwards, or None."""
    f = rec["forwards"]
    return 1e3 * sum(x["decode_s"] for x in f) / len(f) if f else None
