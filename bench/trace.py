"""Reduction of a profiler trace to device busy time, top ops and idle gaps.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``: the
device planes' op events and the host's ``bench.*`` spans, all on the
trace's one clock.  ``reduce`` works on plain (name, start_ns, dur_ns)
tuples, so a test can feed it a synthetic or recorded trace.

Busy time is the union of the device's op intervals inside the traced
window (the host span ``bench.window``), averaged over the devices that ran
anything.  An idle gap is an interval of that window in which no op ran;
each is named by the innermost ``bench.*`` span covering its midpoint, that
is by what the host was doing.  Every op's time inside the window and its
count, by name and summed over the devices, is kept (``ops_s``), so a
reader of a new kernel needs no edit here.
"""
from __future__ import annotations

from pathlib import Path

WINDOW_SPAN = "bench.window"
# Lines of a TPU device plane that hold individual operations; a plane
# without them falls back to whole-program events.
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def load(path) -> dict:
    """{"devices": {plane: [(name, start, dur)]}, "host": [(name, start, dur)]}."""
    from jax.profiler import ProfileData

    files = sorted(Path(path).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(str(files[-1]))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            pick = [n for n in OP_LINES if n in lines] or [n for n in MODULE_LINES if n in lines]
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for n in pick for e in lines[n].events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in ln.events if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(t: float, host) -> str:
    best = None
    for name, s, d in host:
        if name != WINDOW_SPAN and s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "other"


def reduce(trace: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, every op's [seconds, count] by name
    (``ops_s``), and the top device ops and idle gaps."""
    win = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = win[0]
    busy_total, ops, gaps = 0.0, {}, []
    for evs in trace["devices"].values():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in evs if s < w1 and s + d > w0]
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged)
        for name, s, d in evs:
            if s < w1 and s + d > w0:
                row = ops.setdefault(name, [0.0, 0])
                row[0] += min(s + d, w1) - max(s, w0)
                row[1] += 1
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, (a + b) / 2))
    n_dev = max(len(trace["devices"]), 1)
    window_s = (w1 - w0) * 1e-9
    busy_s = busy_total / n_dev * 1e-9
    ops_s = {n: [t * 1e-9, k] for n, (t, k) in ops.items()}
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "ops_s": ops_s,
        "device_ops": [[n, v[0]] for n, v in sorted(ops_s.items(), key=lambda x: -x[1][0])[:top]],
        "idle_gaps": [[_label(mid, trace["host"]), t * 1e-9]
                      for t, mid in sorted(gaps, key=lambda x: -x[0])[:top]],
    }
