"""Reduction of a profiler trace by the program's own spans.

``load`` reads the same ``.xplane.pb`` as ``bench/trace.py``: the program's
host spans with their arguments (every host event that carries a ``window``
argument, which each span ``repro.tracing`` writes while it is on does,
whatever its name), the device planes' op events and their module events
(one per run of a compiled program, named after it: ``jit_decode_step``).
``reduce`` works on plain tuples, so a test can feed it a synthetic trace.

For every span name (``by_name``): how many spans, their host seconds, and
the device-busy seconds inside them, so a reader of a new span needs no
edit here.

Per profiled window (one ``serve.window`` span each):

* idle: time inside the span in which no op ran on the device;
* select: the window's ``serve.select`` time;
* dispatch host: its ``serve.dispatch`` time less the ``exec.prefill`` and
  ``exec.decode`` inside it (padding, merging, token readback, the
  report split);

and per ``exec.forward``, the device-busy time of the decode program's
module events inside it.  Idle time inside the closes is also summed by
the innermost program span covering it.
"""
from __future__ import annotations

import bisect
import itertools
import statistics
from pathlib import Path

WINDOW = "serve.window"
DECODE_MODULE = "jit_decode_step"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def load(path) -> dict:
    """{"ops": {plane: [(name, start, dur)]}, "modules": {plane: [...]},
    "host": [(name, start, dur, args)]}, all in ns on the trace's clock."""
    from jax.profiler import ProfileData

    files = sorted(Path(path).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    ops, modules, host = {}, {}, []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for ln in plane.lines:
                sink = ops if ln.name in OP_LINES else modules if ln.name in MODULE_LINES else None
                if sink is not None:
                    sink.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns)) for e in ln.events)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    args = dict(e.stats)
                    if "window" in args:
                        host.append((e.name, float(e.start_ns), float(e.duration_ns), args))
    return {"ops": ops, "modules": modules, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class _Covered:
    """Disjoint sorted intervals (``_union``'s), with the length of them
    inside any [a, b) found by bisection."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = list(itertools.accumulate((e - s for s, e in merged), initial=0.0))

    def inside(self, a: float, b: float) -> float:
        i = bisect.bisect_right(self.ends, a)  # the first interval ending after a
        j = bisect.bisect_left(self.starts, b)  # past the last one starting before b
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, a - self.starts[i])
                - max(0.0, self.ends[j - 1] - b))


def _idle_pieces(merged, a: float, b: float):
    """The intervals of [a, b) that no merged interval covers."""
    t, out = a, []
    for s, e in merged:
        if e <= t or s >= b:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def _label_idle(pieces, spans):
    """Idle seconds by the innermost span covering each part of ``pieces``."""
    out = {}
    for a, b in pieces:
        cuts = sorted({a, b} | {x for _, s, e in spans for x in (s, e) if a < x < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            best = min(((e - s, n) for n, s, e in spans if s <= mid <= e), default=(0, WINDOW))
            out[best[1]] = out.get(best[1], 0.0) + (hi - lo) * 1e-9
    return out


def reduce(trace: dict) -> dict | None:
    """Per-window, per-forward and per-span-name readings, or None without
    program spans."""
    host = [(n, s, s + d, a) for n, s, d, a in trace["host"]]
    wins = sorted((h for h in host if h[0] == WINDOW), key=lambda h: h[1])
    if not wins:
        return None
    ops = {p: _union((s, s + d) for _, s, d in evs) for p, evs in trace["ops"].items()}
    n_dev = len(ops)
    busy = [_Covered(merged) for merged in ops.values()]
    decode = {p: _union((s, s + d) for n, s, d in evs if n.startswith(DECODE_MODULE))
              for p, evs in trace["modules"].items()}
    decode = [_Covered(iv) for iv in decode.values() if iv]
    windows, idle_by = [], {}
    for _, w0, w1, args in wins:
        inside = [h for h in host if h[0] != WINDOW and w0 <= h[1] and h[2] <= w1]

        def total(n, inside=inside):
            return sum(e - s for m, s, e, _ in inside if m == n) * 1e-9

        idle = None
        if ops:
            idle = 0.0
            for merged in ops.values():
                pieces = _idle_pieces(merged, w0, w1)
                idle += sum(b - a for a, b in pieces) * 1e-9 / n_dev
                for k, v in _label_idle(pieces, [h[:3] for h in inside]).items():
                    idle_by[k] = idle_by.get(k, 0.0) + v / n_dev
        windows.append({
            "window": args.get("window"), "requests": args.get("requests", 0),
            "idle_s": idle, "select_s": total("serve.select"),
            "dispatch_host_s": total("serve.dispatch") - total("exec.prefill")
            - total("exec.decode"),
        })
    forwards = []
    for _, f0, f1, args in sorted((h for h in host if h[0] == "exec.forward"),
                                  key=lambda h: h[1]):
        dev = sum(c.inside(f0, f1) for c in decode) / len(decode) if decode else None
        forwards.append({"model": args.get("model"), "rows": args.get("rows"),
                         "padded": args.get("padded"),
                         "decode_dev_s": None if dev is None else dev * 1e-9})
    by_name = {}
    for n, s, e, _ in host:
        row = by_name.setdefault(n, {"count": 0, "host_s": 0.0, "busy_s": None})
        row["count"] += 1
        row["host_s"] += (e - s) * 1e-9
        if busy:
            inside = sum(c.inside(s, e) for c in busy) / n_dev * 1e-9
            row["busy_s"] = (row["busy_s"] or 0.0) + inside
    return {"windows": windows, "forwards": forwards, "idle_by_span": idle_by,
            "by_name": by_name}


def median_ms(red: dict | None, key: str):
    """Median over the profiled windows of one per-window reading, in ms."""
    vals = [w[key] for w in (red or {}).get("windows", []) if w[key] is not None]
    return 1e3 * statistics.median(vals) if vals else None


def idle_line(red: dict | None) -> str:
    """One line: idle time inside the closes by innermost program span."""
    if not red:
        return "spans: no program spans in the trace"
    parts = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])
    return (f"spans: idle inside {len(red['windows'])} closes by innermost program span: "
            + ", ".join(f"{n} {1e3 * v:.3f} ms" for n, v in parts))
