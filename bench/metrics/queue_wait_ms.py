"""Time a request waits in the window queue (ms): the growth of
``ServeStats.queue_wait_s`` over that of ``ServeStats.queued``, over the
measured windows before the trace."""


def read(rec: dict):
    """Mean per scheduled request, or None where the program has no such counter."""
    w = [x for x in rec["windows"] if "queued" in x]
    n = sum(x["queued"] for x in w)
    return 1e3 * sum(x["queue_wait_s"] for x in w) / n if n else None
