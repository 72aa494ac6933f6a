"""Where a run stopped making progress: a watch thread for host stalls.

Every ``period_s`` the thread notes the time, the innermost frames of the
main thread, and counters of the process and the host: the main thread's
CPU time and its time waiting for a CPU (``schedstat``), its involuntary
context switches, the process's major page faults, the CPU throttling of
its control group (``cpu.stat``), and the host's pressure stall totals for
CPU, memory and I/O (``/proc/pressure``).  After the run
``stalls()`` lists two kinds of stall with what the counters did across
each: a gap of over ``gap_s`` in the thread's own ticks (the whole process
was held), and the main thread found at one place, in one step of the
caller's loop (``progress``), for over ``place_s`` (it was held there).
Counters a machine does not offer read as absent.
"""
from __future__ import annotations

import os
import sys
import threading
import time


def _read(path: str) -> str:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return ""


def counters(tid: int) -> dict:
    """The counters of one sample (milliseconds, or counts)."""
    c = {}
    sched = _read(f"/proc/self/task/{tid}/schedstat").split()
    if len(sched) >= 2:
        c["main_cpu_ms"], c["main_runq_ms"] = int(sched[0]) / 1e6, int(sched[1]) / 1e6
    for line in _read(f"/proc/self/task/{tid}/status").splitlines():
        if line.startswith("nonvoluntary_ctxt_switches"):
            c["main_preempted"] = int(line.split()[1])
    stat = _read("/proc/self/stat").rsplit(")", 1)[-1].split()
    if len(stat) > 9:
        c["major_faults"] = int(stat[9])
    for line in _read("/sys/fs/cgroup/cpu.stat").splitlines():
        key, _, val = line.partition(" ")
        if key == "nr_throttled":
            c["cgroup_throttled"] = int(val)
        elif key == "throttled_usec":
            c["cgroup_throttled_ms"] = int(val) / 1e3
    for kind in ("cpu", "memory", "io"):
        for line in _read(f"/proc/pressure/{kind}").splitlines():
            if line.startswith("some") and "total=" in line:
                c[f"host_{kind}_stall_ms"] = int(line.rsplit("total=", 1)[1]) / 1e3
    return c


def _where(frame, depth: int = 3) -> str:
    parts = []
    while frame is not None and len(parts) < depth:
        code = frame.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno} {code.co_name}")
        frame = frame.f_back
    return " < ".join(parts)


class Watch:
    """A daemon thread sampling the main thread until ``stop()``."""

    def __init__(self, period_s: float = 0.02, gap_s: float = 0.1, place_s: float = 0.3):
        self.period_s, self.gap_s, self.place_s = period_s, gap_s, place_s
        self.progress = 0  # the caller's loop counter
        self.main = threading.main_thread()
        self.samples: list[tuple[float, tuple[int, str], dict]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, name="bench-stall-watch", daemon=True)

    def start(self) -> "Watch":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()

    def _loop(self) -> None:
        tid = self.main.native_id
        while not self._stop.is_set():
            frame = sys._current_frames().get(self.main.ident)
            self.samples.append((time.perf_counter(), (self.progress, _where(frame)),
                                 counters(tid)))
            del frame
            self._stop.wait(self.period_s)

    def stalls(self) -> list[dict]:
        """Each stall: its start (perf_counter), seconds, kind, where the
        main thread was, and the change of every counter across it."""
        s, out = self.samples, []

        def delta(a, b):
            return {k: round(b[2][k] - a[2][k], 3) for k in a[2] if k in b[2]}

        for a, b in zip(s, s[1:]):
            if b[0] - a[0] > self.gap_s:
                out.append({"t": a[0], "s": b[0] - a[0], "kind": "process held",
                            "where": a[1][1], "delta": delta(a, b)})
        i = 0
        while i + 1 < len(s):
            j = i
            while j + 1 < len(s) and s[j + 1][1] == s[i][1]:
                j += 1
            if s[j][0] - s[i][0] > self.place_s:
                out.append({"t": s[i][0], "s": s[j][0] - s[i][0], "kind": "main at one place",
                            "where": s[i][1][1], "delta": delta(s[i], s[j])})
            i = j + 1
        return sorted(out, key=lambda x: x["t"])

    def totals(self) -> dict:
        """The change of every counter over the whole watch."""
        if len(self.samples) < 2:
            return {}
        a, b = self.samples[0][2], self.samples[-1][2]
        return {k: round(b[k] - a[k], 3) for k in a if k in b}
