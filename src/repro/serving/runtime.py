"""Serving runtime: window queue, model-swap manager, batch executor.

This is the *real* execution half of the system (the paper's "worker"):
the scheduler (repro.core) decides (model, order, batch); the runtime
charges swaps and dispatches batches to an ``ExecutorBackend``
(``serving.backends``) — jitted JAX models by default, bucketed
continuous-batching forwards or pure cost-model estimates when a
different backend is passed.  On this CPU container the default backend
runs reduced configs; the same code path drives full configs on a pod
(the jitted step fns are the ones the dry-run compiles).
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import time
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeout
from typing import Callable, Mapping, Optional, Sequence

import numpy as np

from repro import tracing
from repro.core.multiworker import Worker
from repro.core.residency import evict_lru
from repro.core.types import Request, Schedule, ScheduleEntry
from repro.serving.backends import ExecutionReport, ExecutorBackend, ProfiledBackend

__all__ = [
    "WindowQueue",
    "SwapManager",
    "LMExecutor",
    "ExecutionReport",
    "BatchFailure",
    "PoolOutcome",
    "WorkerExecutor",
    "ExecutorPool",
    "LANE_NAMES",
    "PendingExecution",
    "ProcessLaneBackend",
    "require_cpu_platform",
]

# Lane strategies the pool can run its per-worker shares under (see
# ExecutorPool): "serial" executes lanes one after another in the calling
# thread, "thread" (the default, bit-identical to the pre-lane pool) runs
# one long-lived thread per lane, "process" keeps the lane threads for
# coordination but forwards every batch forward to a spawned worker
# process holding its own backend instance — host-side Python (padding,
# fault polling, accounting) stays on the thread while the model forward
# escapes the GIL entirely.  "process" runs on the CPU only: on a chip it
# raises before spawning (see ``require_cpu_platform``).
LANE_NAMES = ("serial", "thread", "process")


def require_cpu_platform(what: str) -> None:
    """Raise before ``what`` spawns processes that each build a JAX
    backend, unless JAX runs on the CPU: an accelerator belongs to one
    process at a time, and this process already holds it."""
    import jax

    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"{what} starts processes that each need the device, but this "
            f"process holds the {platform} device (one process per chip); "
            "use it on the CPU only"
        )


class WindowQueue:
    """Scheduling-window request queue (paper §III-B: requests enqueue
    during a window, then are scheduled as a set)."""

    def __init__(self, window_s: float = 0.1):
        self.window_s = window_s
        self._pending: list[Request] = []

    def submit(self, request: Request):
        """Enqueue a request for the window containing its arrival."""
        self._pending.append(request)

    def drain_window(self, now: float) -> list[Request]:
        """Requests that arrived by ``now`` (window close), ordered by
        (arrival, rid) — the rid tie-break makes simultaneous arrivals
        drain deterministically regardless of submission order."""
        ready = [r for r in self._pending if r.arrival_s <= now]
        self._pending = [r for r in self._pending if r.arrival_s > now]
        return sorted(ready, key=lambda r: (r.arrival_s, r.rid))

    def readmit(self, requests: Sequence[Request]) -> None:
        """Merge withdrawn (preempted) requests back into the queue.

        Their original ``arrival_s`` is in the past, so the next
        ``drain_window`` returns them ahead of fresh arrivals under the
        same deterministic (arrival, rid) order — the re-admission path of
        window-close preemption."""
        self._pending.extend(requests)

    def __len__(self):
        return len(self._pending)


class SwapManager:
    """LRU model residency with byte-accounted capacity.

    ``load(name)`` returns the simulated swap latency (0 when resident)
    and updates residency; actual weight materialization is delegated to
    the executor's lazy param store.  Eviction follows the shared rule in
    ``repro.core.residency`` — the same one the scheduler's
    ``WorkerTimeline`` charges swaps by — so the runtime's realized swap
    pattern matches the scheduler's estimates: oldest-first, and the model
    being loaded is never evicted (a variant larger than capacity resides
    alone rather than thrashing).
    """

    def __init__(self, capacity_bytes: int | None, sizes: Mapping[str, int],
                 load_latency: Mapping[str, float]):
        self.capacity = capacity_bytes
        self.sizes = dict(sizes)
        self.load_latency = dict(load_latency)
        self._resident: OrderedDict[str, int] = OrderedDict()
        self.swap_count = 0
        self.evictions = 0

    def resident_bytes(self) -> int:
        """Total bytes of currently resident model weights."""
        return sum(self._resident.values())

    def is_resident(self, name: str) -> bool:
        """Whether ``name`` is currently resident (no swap charge)."""
        return name in self._resident

    def load(self, name: str) -> float:
        """Make ``name`` resident; returns the swap latency charged."""
        if name in self._resident:
            self._resident.move_to_end(name)
            return 0.0
        self.swap_count += 1
        self._resident[name] = self.sizes.get(name, 0)
        order = list(self._resident)
        for victim in evict_lru(order, self.sizes, self.capacity, protect=name):
            del self._resident[victim]
            self.evictions += 1
        return self.load_latency.get(name, 0.0)


@dataclasses.dataclass
class BatchFailure:
    """One batch that did NOT execute successfully on its lane.

    ``kind`` is an injected fault kind (``crash``/``transient``/
    ``swap_fail``), ``"error"`` for a real exception caught by the
    per-batch guard, or ``"lane"`` for a lane-level failure outside it.
    ``cascaded`` marks batches failed only because an earlier crash
    killed their lane (not independent failure evidence)."""

    worker: int
    request_ids: list
    model: str
    kind: str
    batch_index: int = -1
    cascaded: bool = False
    error: str = ""


@dataclasses.dataclass
class PoolOutcome:
    """Everything ``execute_supervised`` gathered from the lanes: the
    successful reports, the failed batches, and the lanes that blew the
    deadline timeout (joined late; a health signal, not lost work)."""

    reports: list
    failures: list
    timed_out: list

    def failed_rids(self) -> set[int]:
        """Request ids of every failed batch (for withdrawal/retry)."""
        return {rid for f in self.failures for rid in f.request_ids}


class _ImmediateFuture:
    """Future-shaped wrapper around a call that already ran (serial lane)."""

    def __init__(self, fn, args):
        self._exc: BaseException | None = None
        self._res = None
        try:
            self._res = fn(*args)
        except BaseException as err:  # re-raised at result(), like a Future
            self._exc = err

    def result(self, timeout=None):
        """The call's result; ``timeout`` is accepted but meaningless —
        the work already ran at submit time."""
        if self._exc is not None:
            raise self._exc
        return self._res


class _ImmediateExecutor:
    """Executor-shaped serial lane: ``submit`` runs the call inline, in
    submission order, in the calling thread.  The deterministic baseline
    the lane benchmark compares the concurrent strategies against (and
    the right choice when the backend is not thread-safe)."""

    def submit(self, fn, *args) -> _ImmediateFuture:
        return _ImmediateFuture(fn, args)

    def shutdown(self, wait=True):
        """Nothing to tear down (no threads)."""


def _lane_worker_main(conn) -> None:
    """Entry point of one spawned lane worker process.

    Protocol (host side is ``ProcessLaneBackend``): first message is
    ``("init", backend)`` — the pickled (lazy, never-executed) backend
    instance this process owns; then ``("run", model, prompts, rids,
    class_token_ids)`` per batch, answered with ``("ok", prefill_s,
    decode_s, tokens, predictions)`` or ``("err", repr)``; ``("stop",)``
    ends the loop."""
    backend = None
    while True:
        try:
            msg = conn.recv()
        except EOFError:
            return
        if msg[0] == "stop":
            conn.close()
            return
        if msg[0] == "init":
            backend = msg[1]
            conn.send(("ok",))
            continue
        _, model_name, prompts, rids, class_token_ids = msg
        try:
            rep = backend.run_batch(model_name, prompts, rids, class_token_ids)
            conn.send(("ok", rep.prefill_s, rep.decode_s, rep.tokens, rep.predictions))
        except Exception as err:
            conn.send(("err", repr(err)))


class ProcessLaneBackend(ExecutorBackend):
    """Backend proxy that forwards every forward pass to a dedicated
    spawned worker process holding its own backend instance.

    The process-lane half of ``ExecutorPool(lane="process")``: host-side
    lane threads still coordinate (padding, fault polling, dispatch
    marks), but the batch itself — the part that holds the device or, for
    host-bound substrates, the GIL — runs in the worker process.  Work
    ships as plain arrays (padded ``(B, S)`` int32 prompts + request
    ids); reports come back as plain fields, so nothing jitted or
    device-resident ever crosses the pipe.

    ``template`` must be a FRESH (lazy, never-executed) backend — exactly
    what ``spawn()`` returns — so it pickles cleanly into the child.  The
    host keeps it for metadata (sizes, swap costs, provenance) and
    records realized observations proxy-side for ``affine``.  The child
    spawns lazily on first ``run_batch``; ``close()`` stops it.
    """

    def __init__(self, template: ExecutorBackend):
        require_cpu_platform('lane="process"')
        self.template = template
        self.variants = dict(template.variants)
        self.new_tokens = template.new_tokens
        self.provenance = template.provenance
        self._obs = {}
        self._proc = None
        self._conn = None

    def _ensure(self) -> None:
        if self._proc is not None:
            return
        ctx = multiprocessing.get_context("spawn")
        self._conn, child = ctx.Pipe()
        self._proc = ctx.Process(target=_lane_worker_main, args=(child,), daemon=True)
        self._proc.start()
        child.close()
        self._conn.send(("init", self.template))
        ack = self._conn.recv()
        if ack[0] != "ok":  # pragma: no cover - init never computes
            raise RuntimeError(f"lane worker failed to initialize: {ack!r}")

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """Ship one padded batch to the worker process and rebuild the
        report host-side.  Waiting on the pipe releases the GIL, so lane
        threads block here in parallel while their processes compute."""
        self._ensure()
        self._conn.send(("run", model_name, np.ascontiguousarray(prompts),
                         list(request_ids), class_token_ids))
        reply = self._conn.recv()
        if reply[0] != "ok":
            raise RuntimeError(f"lane worker batch failed: {reply[1]}")
        _, prefill_s, decode_s, tokens, predictions = reply
        self._record(model_name, prompts.shape[0], prefill_s + decode_s)
        return ExecutionReport(
            request_ids=list(request_ids), model=model_name,
            batch_size=prompts.shape[0], swap_s=0.0,
            prefill_s=prefill_s, decode_s=decode_s,
            tokens=tokens, predictions=predictions,
        )

    def affine(self, model_name: str):
        """Proxy-side realized fit when batches have run, else the
        template's estimate."""
        if self._obs.get(model_name):
            return super().affine(model_name)
        return self.template.affine(model_name)

    def model_bytes(self, model_name: str, batch: int | None = None,
                    max_len: int | None = None) -> int:
        """Residency footprint, from the template's metadata."""
        return self.template.model_bytes(model_name, batch, max_len)

    def swap_cost(self, model_name: str) -> float:
        """Cold-load seconds, from the template's metadata."""
        return self.template.swap_cost(model_name)

    def spawn(self) -> "ProcessLaneBackend":
        """A fresh proxy over a fresh template (its own child process)."""
        return ProcessLaneBackend(self.template.spawn())

    def close(self) -> None:
        """Stop and join the worker process (idempotent)."""
        if self._proc is None:
            return
        try:
            self._conn.send(("stop",))
        except (BrokenPipeError, OSError):
            pass
        self._conn.close()
        self._proc.join(timeout=5.0)
        if self._proc.is_alive():  # pragma: no cover - stuck child
            self._proc.terminate()
            self._proc.join(timeout=5.0)
        self._proc = None
        self._conn = None


class PendingExecution:
    """Handle to one window's in-flight lane execution
    (``ExecutorPool.execute_async``).

    ``result()`` joins the coordinator and returns the ``PoolOutcome``;
    ``started_at``/``finished_at`` are ``time.perf_counter()`` stamps the
    serving loop uses to measure how much scheduling wall time the
    overlap actually hid."""

    def __init__(self, future: Future, started_at: float):
        self._future = future
        self.started_at = started_at
        self.finished_at: float | None = None

    def done(self) -> bool:
        """Whether the lanes have all finished (non-blocking)."""
        return self._future.done()

    def result(self) -> PoolOutcome:
        """Join the in-flight execution (re-raises lane errors exactly
        like the synchronous path)."""
        outcome, finished = self._future.result()
        self.finished_at = finished
        return outcome


class LMExecutor:
    """Executes scheduled batches through an ``ExecutorBackend``.

    The executor owns the residency accounting (its ``SwapManager``,
    sized by ``backend.model_bytes`` and charged at ``backend.swap_cost``
    per cold load); the backend owns the actual forward passes.  With no
    explicit ``backend`` the default is ``ProfiledBackend`` over
    ``variants`` ({name: (ModelConfig, seed)}) — byte-for-byte the
    pre-backend behavior: weight-only sizes, 25 GB/s staging, jitted
    prefill+decode per scheduled batch.

    Classification convention for the paper's applications: each request
    carries ``features`` already tokenized (prompt ids); the predicted
    class = argmax over the logits of ``class_token_ids`` after prefill.
    """

    def __init__(self, variants: Mapping[str, tuple] | None = None,
                 capacity_bytes: int | None = None, new_tokens: int = 4,
                 backend: ExecutorBackend | None = None):
        if backend is None:
            if variants is None:
                raise ValueError("LMExecutor needs variants=... or backend=...")
            backend = ProfiledBackend(variants, new_tokens=new_tokens)
        self.backend = backend
        self.variants = dict(backend.variants)
        self.new_tokens = backend.new_tokens
        sizes = {name: int(backend.model_bytes(name)) for name in self.variants}
        loads = {name: float(backend.swap_cost(name)) for name in self.variants}
        self.swaps = SwapManager(capacity_bytes, sizes, loads)

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """prompts: (B, S) int32 (pre-padded)."""
        swap_s = self.swaps.load(model_name)
        report = self.backend.run_batch(model_name, prompts, request_ids, class_token_ids)
        report.swap_s = swap_s
        return report

    def close(self) -> None:
        """Release backend resources (e.g. a process lane's worker)."""
        self.backend.close()

    @staticmethod
    def _pad(batch: Sequence[ScheduleEntry],
             prompt_fn: Callable[[Request], np.ndarray]) -> np.ndarray:
        prompts = [prompt_fn(e.request) for e in batch]
        maxlen = max(p.shape[0] for p in prompts)
        padded = np.zeros((len(prompts), maxlen), np.int32)
        for k, p in enumerate(prompts):
            padded[k, :p.shape[0]] = p
        return padded

    def run_entry_batch(self, batch: Sequence[ScheduleEntry],
                        prompt_fn: Callable[[Request], np.ndarray],
                        class_token_ids=None) -> ExecutionReport:
        """Execute ONE batch of schedule entries (same model/batch_id)."""
        if batch[0].model.endswith(":short_circuit"):
            # §V-C1: answered by the SneakPeek stage — no model
            # execution, no swap, no prompt tokenization/padding.
            return ExecutionReport(
                request_ids=[e.request.rid for e in batch], model=batch[0].model,
                batch_size=len(batch), swap_s=0.0, prefill_s=0.0, decode_s=0.0,
                tokens=np.zeros((len(batch), 0), np.int32),
                predictions=[None] * len(batch))
        return self.run_batch(
            batch[0].model, self._pad(batch, prompt_fn),
            [e.request.rid for e in batch], class_token_ids)

    def execute_schedule(self, schedule: Schedule, prompt_fn: Callable[[Request], np.ndarray],
                         class_token_ids=None) -> list[ExecutionReport]:
        """Run a scheduler-produced Schedule batch by batch (grouped entries
        with the same batch_id execute as one padded batch).

        When the backend supports continuous batching (``run_batches``,
        e.g. ``CompiledBackend``), consecutive same-model batches in the
        window fuse into one forward pass; the swap is charged once on
        the run's first report (later batches would have found the model
        resident anyway, a 0-cost load), and per-batch reports come back
        with the fused time split between them.
        """
        batches = list(iter_entry_batches(schedule.sorted_entries()))
        merged_runs = hasattr(self.backend, "run_batches")
        reports: list[ExecutionReport] = []
        i = 0
        while i < len(batches):
            model = batches[i][0].model
            j = i
            if merged_runs and not model.endswith(":short_circuit"):
                while j + 1 < len(batches) and batches[j + 1][0].model == model:
                    j += 1
            if j == i:
                reports.append(self.run_entry_batch(batches[i], prompt_fn, class_token_ids))
            else:
                run = batches[i:j + 1]
                swap_s = self.swaps.load(model)
                merged = self.backend.run_batches(
                    model,
                    [self._pad(b, prompt_fn) for b in run],
                    [[e.request.rid for e in b] for b in run],
                    class_token_ids,
                )
                merged[0].swap_s = swap_s
                reports.extend(merged)
            i = j + 1
        return reports


class WorkerExecutor:
    """One worker's execution lane: a private ``LMExecutor`` (own
    ``SwapManager`` — per-worker residency, exactly what the scheduler's
    per-worker timelines model) plus the ``core.multiworker.Worker``
    whose speed/load scaling it honors.

    All lanes physically share this host's device, so heterogeneity is
    honored in the *accounting*: measured prefill/decode seconds divide
    by ``worker.speed`` and swap seconds multiply by
    ``worker.load_scale``, making reported busy time consistent with the
    scaled profiles Eq. 15 placed the batch with.
    """

    def __init__(self, worker: Worker, variants: Mapping[str, tuple] | None = None,
                 capacity_bytes: int | None = None, new_tokens: int = 4,
                 backend: ExecutorBackend | None = None):
        self.worker = worker
        self.executor = LMExecutor(variants, capacity_bytes, new_tokens, backend=backend)
        self.busy_s = 0.0

    @property
    def swap_count(self) -> int:
        """Weight swaps this lane's SwapManager has performed."""
        return self.executor.swaps.swap_count

    def _scaled(self, report: ExecutionReport) -> ExecutionReport:
        w = self.worker
        if w.speed == 1.0 and w.load_scale == 1.0:
            return report
        return dataclasses.replace(
            report,
            swap_s=report.swap_s * w.load_scale,
            prefill_s=report.prefill_s / w.speed,
            decode_s=report.decode_s / w.speed,
        )

    def execute(
        self,
        entries: Sequence[ScheduleEntry],
        prompt_fn: Callable[[Request], np.ndarray],
        class_token_ids=None,
        until: float | None = None,
        on_dispatch: Callable[[list[int]], None] | None = None,
        injector=None,
        window: int = 0,
        failures: list | None = None,
    ) -> list[ExecutionReport]:
        """Run this worker's share of a placed schedule, batch by batch.

        ``until`` stops dispatch at the first batch whose committed start
        time is at or past it (est_start_s is nondecreasing along a
        worker's queue, so everything later stays backlogged for the next
        window — the half of the schedule window-close preemption may
        withdraw).  ``on_dispatch(rids)`` fires as each batch begins,
        BEFORE execution — the serving loop uses it to set the streaming
        state's dispatch marks so started work is never withdrawn.

        ``injector`` (serving.faults.FaultInjector) is polled per batch
        index within ``window``; ``failures`` (a list the supervised pool
        path passes in) collects ``BatchFailure`` records — injected
        faults AND real per-batch exceptions — instead of raising, so one
        bad batch never takes down the lane's remaining work.  Without a
        ``failures`` sink (the legacy path) exceptions propagate as
        before.  A crash fault stops the lane: its batch and every later
        batch fail (later ones marked ``cascaded``).  A hang fault runs
        the batch and inflates its reported decode seconds by the fault's
        ``delay_s`` — no real sleep; the straggler signal flows through
        the realized-latency EWMA exactly like a genuinely slow lane."""
        if injector is not None and failures is None:
            raise ValueError("fault injection requires a failures sink "
                             "(use ExecutorPool.execute_supervised)")
        reports = []
        wid = self.worker.wid
        crashed = False
        for bi, batch in enumerate(iter_entry_batches(sorted(entries, key=lambda e: e.order))):
            if until is not None and batch[0].est_start_s >= until - 1e-12:
                break
            rids = [e.request.rid for e in batch]
            if crashed:
                failures.append(BatchFailure(
                    worker=wid, request_ids=rids, model=batch[0].model,
                    kind="crash", batch_index=bi, cascaded=True))
                continue
            fault = injector.poll(window, wid, bi, rids) if injector is not None else None
            if fault is not None and fault.kind in ("crash", "transient", "swap_fail"):
                failures.append(BatchFailure(
                    worker=wid, request_ids=rids, model=batch[0].model,
                    kind=fault.kind, batch_index=bi))
                crashed = fault.kind == "crash"
                continue
            if on_dispatch is not None:
                on_dispatch(rids)
            try:
                report = self._scaled(
                    self.executor.run_entry_batch(batch, prompt_fn, class_token_ids)
                )
            except Exception as err:
                if failures is None:
                    raise
                failures.append(BatchFailure(
                    worker=wid, request_ids=rids, model=batch[0].model,
                    kind="error", batch_index=bi, error=repr(err)))
                continue
            if fault is not None and fault.kind == "hang":
                report = dataclasses.replace(
                    report, decode_s=report.decode_s + fault.delay_s)
            report.worker = wid
            self.busy_s += report.total_s
            reports.append(report)
        return reports


class ExecutorPool:
    """The multi-worker execution plane: one ``WorkerExecutor`` lane per
    ``core.multiworker.Worker``, executing each window's placed schedule
    per worker — concurrently, since JAX dispatch releases the GIL while
    device computation runs.

    This is what turns the Eq. 15 placement algebra into realized work:
    ``EdgeServer(workers=[...], executor=...)`` routes every scheduled
    window here instead of the single-``LMExecutor`` path, and feeds the
    per-lane swap counts and busy seconds into ``ServeStats``.
    """

    def __init__(self, workers: Sequence[Worker], variants: Mapping[str, tuple] | None = None,
                 capacity_bytes: int | None = None, new_tokens: int = 4,
                 backend_factory: Callable[[], ExecutorBackend] | None = None,
                 lane: str = "thread"):
        """``backend_factory`` (e.g. ``some_backend.spawn``) is called once
        per lane so every worker gets its own substrate instance — its own
        params, jit caches and residency, as a real per-worker device
        would.  Without it each lane builds the default
        ``ProfiledBackend`` over ``variants``.

        ``lane`` picks the execution strategy per ``LANE_NAMES``:
        ``"thread"`` (default, bit-identical to the pre-lane pool) runs
        lanes on a long-lived thread pool, ``"serial"`` runs them one
        after another in the calling thread, ``"process"`` wraps each
        lane's backend in a ``ProcessLaneBackend`` so forwards run in
        spawned worker processes, outside the GIL."""
        if not workers:
            raise ValueError("ExecutorPool requires at least one worker")
        if variants is None and backend_factory is None:
            raise ValueError("ExecutorPool needs variants=... or backend_factory=...")
        if lane not in LANE_NAMES:
            raise ValueError(f"unknown lane strategy {lane!r}; expected one of {LANE_NAMES}")
        self.lane = lane
        if lane == "process":
            inner = backend_factory or (
                lambda: ProfiledBackend(variants, new_tokens=new_tokens))
            backend_factory = lambda: ProcessLaneBackend(inner())  # noqa: E731
        self.lanes: dict[int, WorkerExecutor] = {
            w.wid: WorkerExecutor(
                w, variants, capacity_bytes, new_tokens,
                backend=backend_factory() if backend_factory is not None else None,
            )
            for w in workers
        }
        self.wall_s = 0.0  # wall-clock spent inside execute_schedule calls
        # One long-lived thread per lane: the serving loop closes a window
        # every ~100 ms, so spawn/join per window would be pure overhead.
        # (Serial lane: an executor-shaped shim that runs work at submit.)
        self._tp: ThreadPoolExecutor | _ImmediateExecutor | None = None
        # Single-thread coordinator for execute_async: runs the whole
        # gather off the caller's thread so scheduling can overlap it.
        self._coord: ThreadPoolExecutor | None = None

    @classmethod
    def from_executor(cls, executor: LMExecutor, workers: Sequence[Worker],
                      lane: str = "thread") -> "ExecutorPool":
        """Build a pool with one lane per worker from a single-executor
        config (same backend config / capacity / new_tokens, one
        ``backend.spawn()`` per lane); each lane still owns its
        residency, as a real per-worker memory would."""
        return cls(
            workers,
            executor.variants,
            capacity_bytes=executor.swaps.capacity,
            new_tokens=executor.new_tokens,
            backend_factory=executor.backend.spawn,
            lane=lane,
        )

    def close(self) -> None:
        """Tear down the lane machinery: the coordinator and lane thread
        pools shut down (waiting for in-flight work) and every lane's
        backend is closed — which for process lanes stops the spawned
        workers.  Idempotent; the pool can be rebuilt lazily afterward,
        but the intended use is ``with ExecutorPool(...) as pool`` or an
        explicit ``close()`` when serving ends."""
        if self._coord is not None:
            self._coord.shutdown(wait=True)
            self._coord = None
        if self._tp is not None:
            self._tp.shutdown(wait=True)
            self._tp = None
        for lane in self.lanes.values():
            lane.executor.close()

    def __enter__(self) -> "ExecutorPool":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    @property
    def swap_counts(self) -> dict[int, int]:
        """Per-worker weight-swap counts (lane SwapManagers)."""
        return {w: lane.swap_count for w, lane in sorted(self.lanes.items())}

    @property
    def cold_forwards(self) -> int:
        """Forwards, over every lane, that ran a shape their lane's
        backend had not run before."""
        return sum(lane.executor.backend.cold_forwards for lane in self.lanes.values())

    @property
    def busy_s(self) -> dict[int, float]:
        """Per-worker busy seconds (scaled swap + prefill + decode)."""
        return {w: lane.busy_s for w, lane in sorted(self.lanes.items())}

    def utilization(self) -> dict[int, float]:
        """Per-worker busy / pool-wall fraction (0.0 before any work)."""
        if self.wall_s <= 0:
            return {w: 0.0 for w in sorted(self.lanes)}
        return {w: lane.busy_s / self.wall_s for w, lane in sorted(self.lanes.items())}

    def execute_schedule(
        self,
        schedule: Schedule,
        prompt_fn: Callable[[Request], np.ndarray],
        class_token_ids=None,
        until: float | None = None,
        on_dispatch: Callable[[list[int]], None] | None = None,
    ) -> list[ExecutionReport]:
        """Execute a placed schedule: entries split by ``entry.worker``,
        each lane running its share in order on its own thread.  ``until``
        and ``on_dispatch`` are forwarded to every lane (see
        ``WorkerExecutor.execute``).  Reports return grouped by worker id,
        each lane's in dispatch order.

        Concurrency contract: ``prompt_fn`` and ``on_dispatch`` are
        invoked from multiple lane threads at once — unlike the
        sequential single-``LMExecutor`` path, they must be thread-safe
        (derive any randomness from the request, e.g. its rid, rather
        than mutating one shared generator).

        Every lane outcome is gathered before anything is raised: one
        lane's exception no longer leaves the other lanes' futures
        undrained or skips the ``wall_s`` accounting — the first failing
        lane's error (ascending worker id) is re-raised only after every
        lane has been joined.

        This IS the supervised gather with its machinery off: no
        injector, no failure sinks, no timeout — ``_gather`` degenerates
        to the plain dispatch loop and lane exceptions propagate instead
        of becoming ``BatchFailure`` records."""
        return self._gather(
            schedule, prompt_fn, class_token_ids, until, on_dispatch,
            injector=None, window=0, timeout_s=None, supervised=False,
        ).reports

    def _split(self, schedule: Schedule) -> dict[int, list[ScheduleEntry]]:
        """Entries per worker id (schedule order), lanes validated and
        the lane thread pool materialized."""
        by_worker: dict[int, list[ScheduleEntry]] = {}
        for e in schedule.sorted_entries():
            by_worker.setdefault(e.worker, []).append(e)
        unknown = set(by_worker) - set(self.lanes)
        if unknown:
            raise KeyError(f"schedule places work on unpooled workers {sorted(unknown)}")
        if self._tp is None:
            if self.lane == "serial":
                self._tp = _ImmediateExecutor()
            else:
                self._tp = ThreadPoolExecutor(max_workers=len(self.lanes))
        return by_worker

    def execute_async(
        self,
        schedule: Schedule,
        prompt_fn: Callable[[Request], np.ndarray],
        class_token_ids=None,
        until: float | None = None,
        on_dispatch: Callable[[list[int]], None] | None = None,
        injector=None,
        window: int = 0,
        timeout_s: float | None = None,
        supervised: bool = True,
    ) -> PendingExecution:
        """Start a window's lane execution WITHOUT joining it: the whole
        gather (dispatch, lane join, ``wall_s`` accounting) runs on a
        dedicated single-thread coordinator, and the returned
        ``PendingExecution`` joins it later — this is what lets the
        serving loop schedule window k+1 while window k's lanes run.

        Semantics are identical to calling ``execute_supervised`` /
        ``execute_schedule`` at the moment ``result()`` is awaited: same
        lane split, same deterministic join order, same failure records;
        unsupervised lane errors re-raise out of ``result()``.  One
        execution may be in flight at a time (the coordinator has one
        thread; a second call queues behind the first)."""
        if self._coord is None:
            self._coord = ThreadPoolExecutor(max_workers=1)
        t0 = time.perf_counter()

        def _run() -> tuple[PoolOutcome, float]:
            outcome = self._gather(
                schedule, prompt_fn, class_token_ids, until, on_dispatch,
                injector, window, timeout_s, supervised,
            )
            return outcome, time.perf_counter()

        return PendingExecution(self._coord.submit(tracing.carry(_run)), t0)

    def execute_supervised(
        self,
        schedule: Schedule,
        prompt_fn: Callable[[Request], np.ndarray],
        class_token_ids=None,
        until: float | None = None,
        on_dispatch: Callable[[list[int]], None] | None = None,
        injector=None,
        window: int = 0,
        timeout_s: float | None = None,
    ) -> PoolOutcome:
        """Supervised lane execution: the fault-tolerant twin of
        ``execute_schedule``.

        Each lane runs with a per-batch failure guard (and the optional
        fault ``injector``, polled per (window, worker, batch)): injected
        faults and real exceptions become ``BatchFailure`` records
        instead of raising, so one bad batch never loses the rest of the
        pool's window.  ``timeout_s`` bounds the wait for the WHOLE
        pool's lanes (a shared deadline from dispatch): a lane that blows
        it is recorded in ``timed_out`` — a health signal — and then
        hard-joined (Python threads cannot be cancelled; the wait just
        stops masking the straggler).  A lane-level exception outside the
        per-batch guard fails the lane's not-yet-accounted batches with
        kind ``"lane"``.

        Returns a ``PoolOutcome``; the serving loop withdraws
        ``failed_rids()`` via ``StreamingState.withdraw`` and re-admits
        them under its retry budget."""
        return self._gather(
            schedule, prompt_fn, class_token_ids, until, on_dispatch,
            injector, window, timeout_s, supervised=True,
        )

    def _gather(
        self,
        schedule: Schedule,
        prompt_fn: Callable[[Request], np.ndarray],
        class_token_ids,
        until: float | None,
        on_dispatch: Callable[[list[int]], None] | None,
        injector,
        window: int,
        timeout_s: float | None,
        supervised: bool,
    ) -> PoolOutcome:
        """The one dispatch loop both public paths share: split entries
        per worker, submit every lane, join in ascending worker id,
        account ``wall_s`` exactly once.

        ``supervised=False`` is the degenerate case — lanes run with no
        failure sink (exceptions propagate), no timeout deadline exists,
        and the first failing lane's error is re-raised after every lane
        has been joined.  ``supervised=True`` hands each lane a
        ``BatchFailure`` sink, converts lane-level exceptions into
        ``kind="lane"`` failures for the lane's unaccounted batches, and
        records (then hard-joins) lanes that blow the shared
        ``timeout_s`` deadline."""
        by_worker = self._split(schedule)
        failures_by: dict[int, list[BatchFailure]] = {wid: [] for wid in by_worker}
        t0 = time.perf_counter()
        # Ascending-wid submission keeps the serial lane's inline
        # execution order deterministic; for the concurrent lanes the
        # order is immaterial (the join below is already sorted).
        futures = {
            wid: self._tp.submit(
                tracing.carry(self.lanes[wid].execute), by_worker[wid], prompt_fn,
                class_token_ids, until, on_dispatch,
                injector, window, failures_by[wid] if supervised else None,
            )
            for wid in sorted(by_worker)
        }
        reports: list[ExecutionReport] = []
        failures: list[BatchFailure] = []
        timed_out: list[int] = []
        errors: dict[int, BaseException] = {}
        deadline = None if timeout_s is None else t0 + timeout_s
        for wid in sorted(futures):
            lane_reports: list[ExecutionReport] = []
            try:
                if deadline is None:
                    lane_reports = futures[wid].result()
                else:
                    remaining = max(0.0, deadline - time.perf_counter())
                    try:
                        lane_reports = futures[wid].result(timeout=remaining)
                    except FuturesTimeout:
                        timed_out.append(wid)
                        lane_reports = futures[wid].result()  # hard join
            except BaseException as err:
                if not supervised:
                    # Gather-all: re-raised below, after every lane joins.
                    errors[wid] = err
                elif isinstance(err, Exception):
                    # Lane-level failure outside the per-batch guard: every
                    # batch not already reported or failed goes down with it.
                    done = {rid for f in failures_by[wid] for rid in f.request_ids}
                    for rep in lane_reports:
                        done.update(rep.request_ids)
                    for bi, batch in enumerate(iter_entry_batches(
                            sorted(by_worker[wid], key=lambda e: e.order))):
                        rids = [e.request.rid for e in batch]
                        if not done.intersection(rids):
                            failures_by[wid].append(BatchFailure(
                                worker=wid, request_ids=rids, model=batch[0].model,
                                kind="lane", batch_index=bi, error=repr(err)))
                    lane_reports = []
                else:
                    raise
            reports.extend(lane_reports)
            failures.extend(failures_by[wid])
        self.wall_s += time.perf_counter() - t0
        if errors:
            raise errors[min(errors)]
        return PoolOutcome(reports=reports, failures=failures, timed_out=timed_out)


def iter_entry_batches(entries: Sequence[ScheduleEntry]):
    """Group an ordered entry list into dispatchable batches: maximal runs
    of consecutive entries sharing (batch_id >= 0, model) — the same
    grouping rule ``evaluate`` replays with, so realized batches match the
    scheduler's batching decisions."""
    i = 0
    while i < len(entries):
        j = i
        while (
            j + 1 < len(entries)
            and entries[j + 1].batch_id == entries[i].batch_id
            and entries[i].batch_id >= 0
            and entries[j + 1].model == entries[i].model
        ):
            j += 1
        yield entries[i : j + 1]
        i = j + 1
