"""EdgeServer: the end-to-end serving loop (paper Fig. 1).

    data streams -> SneakPeek stage -> window queue -> scheduler
        -> (grouped, model-selected, placed) schedule -> executor -> results

Components are the real ones: the scheduler is ``repro.core`` (any of
the five policies), the SneakPeek stage computes k-NN Dirichlet
posteriors, and the executor runs actual JAX models (reduced configs on
CPU, pod configs via the same jitted steps).  With ``workers=[...]`` the
execution plane is an ``ExecutorPool`` — one lane per worker, running
each window's Eq. 15 placement concurrently — and ``preempt=True``
additionally withdraws committed-but-unstarted work at every window
close and re-schedules it under the fresh pool state (see
``repro.core.streaming``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Mapping, Optional

import numpy as np

from repro import tracing
from repro.core.evaluation import evaluate
from repro.core.scheduler import SchedulerPolicy, effective_apps, schedule_window
from repro.core.streaming import StreamingState
from repro.core.types import Application, Request
from repro.serving.runtime import ExecutorPool, LMExecutor, WindowQueue

__all__ = ["EdgeServer", "ServeStats"]


@dataclasses.dataclass
class ServeStats:
    """Aggregate serving metrics accumulated across windows."""

    windows: int = 0
    requests: int = 0
    violations: int = 0
    swaps: int = 0
    mean_utility: float = 0.0
    scheduling_overhead_s: float = 0.0
    # Per-worker busy seconds (swap + execution) accumulated at commit
    # time from the streaming state's replay, and the served makespan
    # (busiest worker's committed busy-until time).
    worker_busy_s: dict = dataclasses.field(default_factory=dict)
    span_s: float = 0.0
    # Executor-pool realized metrics (multi-worker execution plane):
    # per-lane weight-swap counts and scaled busy seconds, fed from the
    # pool after each window's dispatch.
    worker_swaps: dict = dataclasses.field(default_factory=dict)
    pool_busy_s: dict = dataclasses.field(default_factory=dict)
    # Window-close preemption: requests withdrawn for re-scheduling, and
    # withdrawn requests dropped because their deadline had passed (each
    # dropped request keeps a recorded violation and zero utility).
    preempted: int = 0
    dropped: int = 0
    # Fault-tolerant closed loop (``faults``/``health``): batch failures
    # observed on the lanes, failed requests re-admitted for retry,
    # requests dropped after exhausting the retry budget (or their
    # deadline), retries whose original variant no longer fit the
    # remaining slack (the accuracy-scaling fallback path), workers
    # currently quarantined, and the per-worker realized/committed
    # latency-ratio EWMA driving drift correction.
    failed_batches: int = 0
    retries: int = 0
    dropped_after_retry: int = 0
    fallbacks: int = 0
    quarantined_workers: int = 0
    realized_over_profiled: dict = dataclasses.field(default_factory=dict)
    # Per-variant latency provenance ({model name -> profiled|costmodel|
    # realized}): which kind of estimate ``realized_over_profiled`` is
    # correcting for the variants this server schedules.
    profile_provenance: dict = dataclasses.field(default_factory=dict)
    # Schedule/execute overlap accounting: host seconds spent in the
    # decision phases (drain + schedule + commit), lane seconds spent
    # executing dispatched windows, and — with ``overlap=True`` — the
    # portion of decision time that ran hidden under the previous
    # window's lane execution instead of serializing after it.
    sched_wall_s: float = 0.0
    exec_wall_s: float = 0.0
    overlap_saved_s: float = 0.0
    # Queueing: seconds the scheduled requests waited in the window queue
    # (close time less arrival, summed) and how many were scheduled — a
    # re-admitted request counts again at its next window.  Forwards that
    # ran a shape their backend had not run before (a compile, on a
    # jitting backend; ``CompiledBackend`` counts them).
    queue_wait_s: float = 0.0
    queued: int = 0
    cold_forwards: int = 0

    @property
    def worker_utilization(self) -> dict:
        """Busy-time / wall fraction per worker id over the served span
        (0.0 for workers that never received work)."""
        if self.span_s <= 0:
            return {w: 0.0 for w in sorted(self.worker_busy_s)}
        return {
            w: busy / self.span_s
            for w, busy in sorted(self.worker_busy_s.items())
        }

    def as_dict(self):
        """Dataclass fields plus the derived per-worker utilization."""
        out = dataclasses.asdict(self)
        out["worker_utilization"] = self.worker_utilization
        return out


class EdgeServer:
    """Windowed serving loop: queue -> scheduler -> streaming commit -> executor."""

    def __init__(
        self,
        apps: Mapping[str, Application],
        policy: SchedulerPolicy,
        executor: Optional[LMExecutor] = None,
        sneakpeeks=None,
        short_circuit: bool = False,
        window_s: float = 0.1,
        prompt_fn: Optional[Callable[[Request], np.ndarray]] = None,
        workers=None,
        memory_capacity_bytes: int | None = None,
        pipeline: bool = False,
        chunk: int | None = None,
        shard=False,
        preempt: bool = False,
        faults=None,
        health=False,
        retry_budget: int = 2,
        lane_timeout_s: float | None = None,
        backend=None,
        overlap: bool = False,
        lane: str = "thread",
    ):
        """``workers`` (a sequence of ``core.multiworker.Worker``) switches
        scheduling to §VII multi-worker placement; without it the policy
        schedules the single worker 0.  ``pipeline`` feeds every window
        through a persistent ``core.pipeline.WindowPipeline`` (fused
        jitted Eq. 9/12 + Eq. 2/13 selection, compiled once and reused
        across windows) and COMPOSES with ``workers`` — placement then
        runs through the compiled Eq. 15 program — and with
        ``memory_capacity_bytes`` (capacity-aware LRU residency inside
        the compiled selectors).  ``chunk`` sizes the pipeline's
        speculative chunked selection (bit-identical decisions; ``None``
        defers to the policy's ``chunk`` field, 0 = sequential scan).
        ``shard`` routes windows through the device-sharded
        ``core.shard.ShardedWindowPipeline`` (True = every local device,
        int = pinned count; implies ``pipeline`` and composes with
        ``chunk``/``overlap`` — decisions stay bit-identical).

        ``executor`` may be a single ``LMExecutor`` or an
        ``ExecutorPool``; with ``workers`` set, a single executor is
        wrapped into a pool (one lane per worker, same variants) so each
        window's placed schedule actually runs per worker, concurrently.

        ``preempt=True`` enables window-close preemption: at every close,
        backlogged-but-unstarted entries (committed by the scheduler but
        not yet dispatched by the pool) are withdrawn, merged into the
        next window's queue, and re-scheduled under the fresh posteriors
        and pool state; withdrawn entries already past their deadline are
        dropped with a recorded violation.  Off by default — with
        ``preempt=False`` every scheduling decision is bit-identical to
        the non-preemptive server.

        ``faults`` (a ``serving.faults.FaultPlan`` or ``FaultInjector``)
        and/or ``health`` (True, or a ``core.health.HealthTracker``)
        switch execution to the fault-tolerant closed loop: lanes run
        under ``ExecutorPool.execute_supervised`` (per-batch fault
        isolation + the ``lane_timeout_s`` shared deadline), failed
        batches are withdrawn from the committed timelines
        (``StreamingState.withdraw``) and re-admitted with exponential
        backoff up to ``retry_budget`` retries (then dropped with a
        recorded violation), and the tracker's realized/committed EWMA
        feeds latency-scale drift corrections and quarantine masks back
        into the next window's scheduling.  Both default off; the
        defaults leave every existing path bit-identical.

        ``backend`` (a ``serving.backends.ExecutorBackend``) selects the
        execution substrate without hand-building an executor: an
        ``LMExecutor`` is wrapped around it, and — because a non-default
        backend knows its variants' true footprints — the scheduler's
        capacity-aware residency sizes are re-registered from
        ``backend.model_bytes`` (weights + KV cache) instead of the
        asserted ``ModelProfile.memory_bytes`` constants.  Mutually
        exclusive with ``executor``; with neither passed (the default)
        nothing changes.

        ``overlap=True`` double-buffers the serving loop: while window
        k's lanes execute asynchronously, the host drains and schedules
        window k+1 against a snapshot of the committed timelines, then
        reconciles at k+1's commit — window k's realized latencies,
        health/quarantine changes, preemption withdrawals, and fault
        retries all land first, and the speculative schedule is kept
        only when none of them changed the scheduling inputs (otherwise
        it is recomputed, yielding EXACTLY the synchronous decision).
        ``overlap=False`` (the default) is bit-identical to the
        synchronous loop.  ``lane`` selects the pool's execution
        strategy (``serving.runtime.LANE_NAMES``) when this server
        builds the pool; pass a pre-built ``ExecutorPool(lane=...)``
        to control it directly."""
        self.apps = dict(apps)
        self.policy = policy
        if backend is not None:
            if executor is not None:
                raise ValueError("pass either executor=... or backend=..., not both")
            executor = LMExecutor(capacity_bytes=memory_capacity_bytes, backend=backend)
        self.executor = executor
        self.sneakpeeks = sneakpeeks
        self.short_circuit = short_circuit
        self.queue = WindowQueue(window_s)
        self.prompt_fn = prompt_fn
        self.stats = ServeStats()
        self._utility_sum = 0.0
        self.preempt = bool(preempt)
        # Per-request realized (utility, violated) records — the preempt
        # accounting unit: a re-scheduled request OVERWRITES its record,
        # so withdrawn work is never double-counted.  The aggregates are
        # maintained incrementally (_set_record), not by rescanning the
        # whole history every window.
        self._records: dict[int, tuple[float, bool]] = {}
        self._records_utility = 0.0
        self._records_violations = 0
        self.workers = list(workers) if workers else None
        self.num_workers = len(self.workers) if self.workers else 1
        self.pool = None
        if self.workers and executor is not None:
            if isinstance(executor, ExecutorPool):
                if lane != "thread" and executor.lane != lane:
                    raise ValueError(
                        f"lane={lane!r} conflicts with the passed pool's "
                        f"lane={executor.lane!r}; set it on the ExecutorPool")
                self.pool = executor
            else:
                self.pool = ExecutorPool.from_executor(executor, self.workers, lane=lane)
        elif isinstance(executor, ExecutorPool):
            raise ValueError("ExecutorPool requires workers=[...] placement")
        self.overlap = bool(overlap)
        if self.overlap and (self.pool is None or self.prompt_fn is None):
            raise ValueError(
                "overlap=True requires workers=[...], an executor, and "
                "prompt_fn=... (the overlapped loop dispatches windows to "
                "ExecutorPool lanes asynchronously)")
        # In-flight overlapped window: (PendingExecution, its schedule,
        # its close time) — settled by _join_inflight before the next
        # window's commit is finalized.
        self._inflight = None
        self.retry_budget = int(retry_budget)
        self.lane_timeout_s = lane_timeout_s
        self.injector = None
        if faults is not None:
            from repro.serving.faults import FaultInjector, FaultPlan

            self.injector = (
                FaultInjector(faults) if isinstance(faults, FaultPlan) else faults
            )
        self.health = None
        if health:
            from repro.core.health import HealthTracker

            if isinstance(health, HealthTracker):
                self.health = health
            else:
                wids = [w.wid for w in self.workers] if self.workers else [0]
                self.health = HealthTracker(wids)
        self._closed_loop = self.injector is not None or self.health is not None
        if self._closed_loop and self.pool is None:
            raise ValueError(
                "faults/health require workers=[...] and an executor "
                "(the closed loop supervises ExecutorPool lanes)"
            )
        # Accounting unit: per-request records whenever work can be
        # re-scheduled (preemption OR the closed loop's retries), so a
        # retried request overwrites rather than double-counts.
        self._use_records = self.preempt or self._closed_loop
        self._window_index = 0
        self._attempts: dict[int, int] = {}
        self._retry_ready: list[tuple[float, Request]] = []
        # Streaming state: per-worker backlog + model residency carried
        # across windows (scheduling peeks it, evaluation commits to it).
        self.state = StreamingState(
            num_workers=self.num_workers,
            memory_capacity_bytes=memory_capacity_bytes,
            worker_ids=[w.wid for w in self.workers] if self.workers else None,
        )
        self._eff_apps = effective_apps(self.apps, sneakpeeks, short_circuit)
        self.stats.profile_provenance = {
            m.name: m.provenance
            for app in self._eff_apps.values()
            for m in app.models
        }
        # A non-default backend knows the true per-variant footprint
        # (weights + KV cache), so the scheduler's capacity-aware LRU
        # sizes come from it rather than the asserted profile constants.
        # The default ProfiledBackend does NOT re-register: its sizes are
        # weight-only and the pre-backend behavior kept the profiles' —
        # bit-identical defaults.
        exec_backend = getattr(self.executor, "backend", None)
        if exec_backend is not None and exec_backend.provenance != "profiled":
            self.state.register_sizes({
                name: int(exec_backend.model_bytes(name))
                for name in exec_backend.variants
            })
        self._pipeline = None
        if shard:
            from repro.core.shard import ShardedWindowPipeline

            self._pipeline = ShardedWindowPipeline(
                self._eff_apps, sneakpeeks=sneakpeeks, policy=policy,
                workers=self.workers, chunk=chunk, shard=shard,
            )
        elif pipeline:
            from repro.core.pipeline import WindowPipeline

            self._pipeline = WindowPipeline(
                self._eff_apps, sneakpeeks=sneakpeeks, policy=policy,
                workers=self.workers, chunk=chunk,
            )

    def submit(self, request: Request):
        """Enqueue one request for the window containing its arrival."""
        self.queue.submit(request)

    def _preempt_window(self, now: float) -> int:
        """Window-close preemption: withdraw committed-but-unstarted work
        from the streaming state, drop what already expired (recorded
        violation, zero utility), re-admit the rest through the queue.
        Returns the withdrawal count (the overlapped loop keeps its
        speculative schedule only when this is zero)."""
        readmit, expired = self.state.preempt(now)
        self.stats.preempted += len(readmit) + len(expired)
        for r in expired:
            # A close can drop work even when it drains no new requests,
            # so the aggregates update here too, not just in _account.
            self._set_record(r.rid, 0.0, True)
        self.stats.dropped += len(expired)
        if readmit:
            self.queue.readmit(readmit)
        return len(readmit) + len(expired)

    def _set_record(self, rid: int, utility: float, violated: bool) -> None:
        """Insert or overwrite one per-request record, adjusting the
        running aggregates incrementally (a re-scheduled request's stale
        contribution is subtracted before its new one is added)."""
        old = self._records.get(rid)
        if old is not None:
            self._records_utility -= old[0]
            self._records_violations -= int(old[1])
        self._records[rid] = (utility, violated)
        self._records_utility += utility
        self._records_violations += int(violated)
        self.stats.requests = len(self._records)
        self.stats.violations = self._records_violations
        self.stats.mean_utility = self._records_utility / len(self._records)

    def _account(self, sched, res) -> None:
        """Fold one evaluated window into the aggregate stats.

        Non-preemptive servers accumulate sums directly (a request is
        scheduled exactly once).  Preemptive and closed-loop servers keep
        per-request records instead: a re-scheduled (or retried) request
        overwrites its earlier (stale) utility/violation, so totals
        always reflect the LAST commitment for each request."""
        if not self._use_records:
            self.stats.requests += len(res.utilities)
            self.stats.violations += res.violations
            self._utility_sum += res.utilities.sum()
            self.stats.mean_utility = self._utility_sum / max(self.stats.requests, 1)
            return
        over = res.completions > res.deadlines
        for e, u, miss in zip(sched.sorted_entries(), res.utilities, over):
            self._set_record(e.request.rid, float(u), bool(miss))

    def _schedule_requests(self, requests, now: float, state):
        """The decision phase both loop modes share: posterior attach /
        pipeline ingest, then policy scheduling against ``state`` under
        the current drift scales and quarantine mask.  Returns
        ``(schedule, effective apps, evaluate's latency-scale fn)``."""
        from repro.core.sneakpeek import attach_sneakpeek

        lat_scale = mask = scale_fn = None
        if self.health is not None:
            scale_fn = self.health.scale_fn()
            if self.workers:
                lat_scale = self.health.latency_scale()
                mask = self.health.active_wids(self.workers)
        if self._pipeline is not None:
            # Fused data plane: batched ingest + compiled window program
            # (reused across windows), peeking the carried state.  Ingest
            # skips re-admitted requests (evidence drawn once).
            with tracing.span("serve.ingest"):
                self._pipeline.ingest(requests)
            with tracing.span("serve.select"):
                sched = self._pipeline.schedule(
                    requests, now, state=state,
                    lat_scale=lat_scale, worker_mask=mask,
                )
            eff_apps = self._eff_apps
        else:
            if self.sneakpeeks:
                with tracing.span("serve.ingest"):
                    attach_sneakpeek(requests, self.apps, self.sneakpeeks)
            with tracing.span("serve.select"):
                sched, eff_apps = schedule_window(
                    self.policy, requests, self._eff_apps, now,
                    workers=self.workers, state=state,
                    lat_scale=lat_scale, worker_mask=mask,
                )
        return sched, eff_apps, scale_fn

    def _commit_window(self, sched, eff_apps, now: float, scale_fn) -> object:
        """Evaluate a scheduled window against the committed state and
        fold the result into the aggregate stats (shared by both loop
        modes; identical math)."""
        with tracing.span("serve.commit"):
            res = evaluate(
                sched, eff_apps, now, acc_mode="oracle", state=self.state,
                latency_scale=scale_fn,
            )
            self.stats.windows += 1
            self._account(sched, res)
        self.stats.scheduling_overhead_s += sched.scheduling_overhead_s
        # Per-worker utilization, fed from the streaming state at commit:
        # this window's realized busy seconds plus the pool's committed
        # busy-until horizon.
        for w, busy in res.worker_busy_s.items():
            self.stats.worker_busy_s[w] = self.stats.worker_busy_s.get(w, 0.0) + busy
        self.stats.span_s = max(
            self.stats.span_s, max(tl.t for _, tl in self.state.items())
        )
        return res

    def _count_queued(self, requests, now: float) -> None:
        """Fold a window's scheduled requests into the queue-wait totals."""
        self.stats.queued += len(requests)
        self.stats.queue_wait_s += sum(now - r.arrival_s for r in requests)

    def _readmit_due_retries(self, now: float) -> list:
        """Backed-off retries whose ready time has arrived re-enter
        through the queue like preempted work; returns them."""
        due = [r for t, r in self._retry_ready if t <= now]
        if due:
            self._retry_ready = [(t, r) for t, r in self._retry_ready if t > now]
            self.queue.readmit(sorted(due, key=lambda r: (r.arrival_s, r.rid)))
        return due

    def _copy_exec_counters(self) -> None:
        """Copy the execution plane's swap and cold-forward counts (and a
        pool's per-lane swaps and busy seconds) into the stats."""
        if self.pool is not None:
            self.stats.swaps = sum(self.pool.swap_counts.values())
            self.stats.worker_swaps = dict(self.pool.swap_counts)
            self.stats.pool_busy_s = dict(self.pool.busy_s)
            self.stats.cold_forwards = self.pool.cold_forwards
        else:
            self.stats.swaps = self.executor.swaps.swap_count
            self.stats.cold_forwards = self.executor.backend.cold_forwards

    def run_window(self, now: float):
        """Close the current window: (optionally) preempt, re-admit due
        retries, schedule (drift-corrected, health-masked), commit, and
        execute (supervised when the closed loop is on).  With
        ``overlap=True`` execution is dispatched asynchronously and the
        NEXT close schedules against a snapshot while it runs."""
        widx = self._window_index
        self._window_index += 1
        win = tracing.window(widx)
        with win:
            close = self._run_window_overlap if self.overlap else self._run_window_sync
            out = close(now, widx)
            win.set_metadata(requests=0 if out is None else len(out["schedule"]))
        return out

    def _run_window_sync(self, now: float, widx: int):
        """One close of the synchronous loop."""
        t_host0 = time.perf_counter()
        with tracing.span("serve.drain"):
            if self.preempt:
                self._preempt_window(now)
            self._readmit_due_retries(now)
            requests = self.queue.drain_window(now)
        if not requests:
            self._close_health_window()
            return None
        self._count_queued(requests, now)
        sched, eff_apps, scale_fn = self._schedule_requests(requests, now, self.state)
        res = self._commit_window(sched, eff_apps, now, scale_fn)
        self.stats.sched_wall_s += time.perf_counter() - t_host0

        reports = None
        outcome = None
        if self.executor is not None and self.prompt_fn is not None:
            # With preemption on, only batches committed to start inside
            # the upcoming window are dispatched (and marked so in the
            # state); the rest stays backlogged, revisable at the next
            # close.
            until = now + self.queue.window_s if self.preempt else None
            on_dispatch = self.state.mark_dispatched if self.preempt else None
            t1 = time.perf_counter()
            with tracing.span("serve.dispatch"):
                if self._closed_loop:
                    # Supervised execution plane: per-batch fault
                    # isolation, lane deadline, and the failure records
                    # the retry loop consumes.
                    outcome = self.pool.execute_supervised(
                        sched, self.prompt_fn, until=until, on_dispatch=on_dispatch,
                        injector=self.injector, window=widx,
                        timeout_s=self.lane_timeout_s,
                    )
                    reports = outcome.reports
                elif self.pool is not None:
                    # Multi-worker execution plane: each lane runs its
                    # share of the placed schedule concurrently.
                    reports = self.pool.execute_schedule(
                        sched, self.prompt_fn, until=until, on_dispatch=on_dispatch)
                else:
                    reports = self.executor.execute_schedule(sched, self.prompt_fn)
            self.stats.exec_wall_s += time.perf_counter() - t1
            self._copy_exec_counters()
            if outcome is not None:
                self._absorb_outcome(outcome, sched, now)
        self._close_health_window()
        return {"schedule": sched, "eval": res, "reports": reports, "outcome": outcome}

    def _health_signature(self):
        """Equality token over the health tracker's scheduler-facing
        control state (quarantine mask + quantized drift scales); ``None``
        when no tracker runs."""
        if self.health is None:
            return None
        return self.health.control_signature(self.workers or [])

    def _speculate(self, now: float):
        """Drain the upcoming window and schedule it against a CLONE of
        the committed timelines, while the previous window's lanes are
        still executing.  Captures the scheduling-input signatures
        (timelines + health control state) the reconcile step compares
        against after the in-flight outcome lands.

        Safe concurrently with lane execution: lanes only set dispatch
        marks (never timelines), scheduling only peeks the clone, and
        ``evaluate`` has not run — nothing commits here."""
        with tracing.span("serve.drain"):
            requests = self.queue.drain_window(now)
        if not requests:
            return None
        state_sig = self.state.signature()
        health_sig = self._health_signature()
        sched, eff_apps, _ = self._schedule_requests(requests, now, self.state.clone())
        return {
            "requests": requests, "sched": sched, "eff_apps": eff_apps,
            "state_sig": state_sig, "health_sig": health_sig,
        }

    def _join_inflight(self) -> None:
        """Settle the in-flight overlapped window exactly as the
        synchronous loop would have at ITS close: join the lanes, update
        pool stats, absorb the supervised outcome (drift observations,
        failure withdrawals, retries — stamped with the in-flight
        window's own close time, so retry backoffs match the synchronous
        loop), and pay the owed health tick."""
        if self._inflight is None:
            return
        pending, sched, now_k = self._inflight
        self._inflight = None
        outcome = pending.result()
        self._copy_exec_counters()
        self.stats.exec_wall_s += pending.finished_at - pending.started_at
        if self._closed_loop:
            self._absorb_outcome(outcome, sched, now_k)
        self._close_health_window()

    def _run_window_overlap(self, now: float, widx: int):
        """One close of the double-buffered loop.

        Phases: (1) SPECULATE — drain and schedule this window against a
        snapshot while the previous window's lanes still run; (2) JOIN —
        settle the in-flight outcome (realized latencies, withdrawals,
        retries, health tick); (3) RECONCILE — keep the speculative
        schedule only if nothing the join (or preemption) did changed
        this window's scheduling inputs, otherwise re-admit the drained
        requests and recompute, which reproduces the synchronous
        decision exactly; (4) COMMIT + DISPATCH — evaluate against the
        real state and hand the schedule to the lanes asynchronously."""
        t_spec0 = time.perf_counter()
        spec = self._speculate(now) if self._inflight is not None else None
        t_spec1 = time.perf_counter()
        pending_prev = self._inflight[0] if self._inflight is not None else None
        self._join_inflight()
        if pending_prev is not None and pending_prev.finished_at is not None:
            # Decision time that ran while the lanes were still busy.
            self.stats.overlap_saved_s += max(
                0.0,
                min(t_spec1, pending_prev.finished_at)
                - max(t_spec0, pending_prev.started_at),
            )
        t_host0 = time.perf_counter()
        with tracing.span("serve.drain"):
            withdrawn = self._preempt_window(now) if self.preempt else 0
            due = self._readmit_due_retries(now)
        valid = (
            spec is not None
            and withdrawn == 0
            and not due
            and spec["health_sig"] == self._health_signature()
            and spec["state_sig"] == self.state.signature()
        )
        if valid:
            requests = spec["requests"]
            sched, eff_apps = spec["sched"], spec["eff_apps"]
            scale_fn = self.health.scale_fn() if self.health is not None else None
        else:
            with tracing.span("serve.drain"):
                if spec is not None:
                    # The speculative drain is rolled back through the
                    # queue; the re-drain merges it with preempted/retried
                    # work under the same deterministic (arrival, rid)
                    # order.
                    self.queue.readmit(spec["requests"])
                requests = self.queue.drain_window(now)
            if not requests:
                self._close_health_window()
                self.stats.sched_wall_s += (t_spec1 - t_spec0) + (
                    time.perf_counter() - t_host0)
                return None
            sched, eff_apps, scale_fn = self._schedule_requests(
                requests, now, self.state)
        self._count_queued(requests, now)
        res = self._commit_window(sched, eff_apps, now, scale_fn)
        with tracing.span("serve.dispatch"):
            pending = self.pool.execute_async(
                sched,
                self.prompt_fn,
                until=now + self.queue.window_s if self.preempt else None,
                on_dispatch=self.state.mark_dispatched if self.preempt else None,
                injector=self.injector if self._closed_loop else None,
                window=widx,
                timeout_s=self.lane_timeout_s if self._closed_loop else None,
                supervised=self._closed_loop,
            )
        self._inflight = (pending, sched, now)
        self.stats.sched_wall_s += (t_spec1 - t_spec0) + (
            time.perf_counter() - t_host0)
        return {"schedule": sched, "eval": res, "reports": None,
                "outcome": None, "pending": pending}

    def close(self) -> None:
        """Shut down the execution plane: join any in-flight overlapped
        window, then tear down the pool's lane machinery (threads,
        spawned processes) and the single executor's backend."""
        self._join_inflight()
        if self.pool is not None:
            self.pool.close()
        if self.executor is not None and not isinstance(self.executor, ExecutorPool):
            self.executor.close()

    def __enter__(self) -> "EdgeServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    def _close_health_window(self) -> None:
        """Tick the health tracker at window close: quarantine cooldowns
        count down (released workers re-probe) and the fault/drift stats
        snapshot refreshes."""
        if self.health is None:
            return
        self.health.close_window()
        self.stats.quarantined_workers = len(self.health.quarantined())
        self.stats.realized_over_profiled = self.health.ratio_snapshot()

    def _absorb_outcome(self, outcome, sched, now: float) -> None:
        """Fold one supervised window back into the closed loop.

        Successful reports feed the drift EWMA (realized vs committed
        latency per (worker, model)); failures and lane timeouts feed the
        health state machine; every failed request's batch is withdrawn
        from the committed timelines and sent through ``_retry``."""
        ent_by_rid = {e.request.rid: e for e in sched.sorted_entries()}
        if self.health is not None:
            for rep in outcome.reports:
                if not rep.request_ids:
                    continue
                e = ent_by_rid.get(rep.request_ids[0])
                if e is not None and rep.worker >= 0:
                    self.health.observe(rep.worker, rep.model, rep.total_s, e.est_latency_s)
            for wid in outcome.timed_out:
                self.health.record_failure(wid, "timeout")
        failed_model: dict[int, str] = {}
        for f in outcome.failures:
            self.stats.failed_batches += 1
            if self.health is not None and not f.cascaded:
                self.health.record_failure(f.worker, f.kind)
            for rid in f.request_ids:
                failed_model[rid] = f.model
        if not failed_model:
            return
        removed = self.state.withdraw(set(failed_model))
        for r in removed:
            self._retry(r, failed_model.get(r.rid, ""), now)

    def _retry(self, r: Request, model: str, now: float) -> None:
        """Deadline-aware retry with accuracy-scaling fallback.

        The request is dropped (recorded violation, zero utility) when its
        deadline passed, the retry budget is spent, or even the cheapest
        variant cannot finish in the remaining slack.  Otherwise it is
        re-admitted after an exponential backoff
        (``(2**(attempts-1) - 1) * window_s``); if the ORIGINAL variant no
        longer fits the slack, the re-schedule will naturally prefer a
        cheaper (lower-accuracy) one — counted as a fallback."""
        attempts = self._attempts.get(r.rid, 0) + 1
        self._attempts[r.rid] = attempts
        app = self._eff_apps[r.app]
        min_lat = min(m.latency_s for m in app.models)
        if (
            r.deadline_s <= now
            or attempts > self.retry_budget
            or now + min_lat > r.deadline_s
        ):
            self._set_record(r.rid, 0.0, True)
            self.stats.dropped_after_retry += 1
            return
        orig = next((m for m in app.models if m.name == model), None)
        if orig is not None and now + orig.latency_s > r.deadline_s:
            self.stats.fallbacks += 1
        self.stats.retries += 1
        backoff = (2 ** (attempts - 1) - 1) * self.queue.window_s
        self._retry_ready.append((now + backoff, r))

    def run(self, requests, horizon_s: float | None = None):
        """Feed a request trace through windowed scheduling.

        ``horizon_s=None`` (the default) serves until the last arrival;
        an explicit horizon — including ``0.0`` — is honored as given.

        A preemptive server with an executor pool gates dispatch to the
        upcoming window, so after the horizon it keeps closing windows
        until every committed batch has been dispatched (or withdrawn
        and dropped as expired) — otherwise work gated out of the FINAL
        window would silently never run while still counting as served.
        """
        for r in sorted(requests, key=lambda x: x.arrival_s):
            self.submit(r)
        t_end = horizon_s if horizon_s is not None else max(r.arrival_s for r in requests)
        n_windows = int(np.ceil(t_end / self.queue.window_s)) or 1
        outs = []
        for w in range(1, n_windows + 1):
            out = self.run_window(w * self.queue.window_s)
            if out:
                outs.append(out)
        if (
            (self.preempt or self._closed_loop)
            and self.pool is not None
            and self.prompt_fn is not None
        ):
            # Flush: each extra close withdraws/re-schedules the
            # still-undispatched tail (preempt), re-admits due retries
            # (closed loop), and dispatches what now starts inside the
            # next window.  Retry budgets and the committed horizon are
            # finite, so this terminates; the cap is a safety net only.
            # The overlapped loop joins its in-flight window FIRST: the
            # condition reads retry and backlog state that only settles
            # once the outcome is absorbed (a no-op when synchronous).
            while w < n_windows + 10_000:
                self._join_inflight()
                if not (
                    len(self.queue)
                    or self._retry_ready
                    or (self.preempt and self.state.undispatched_backlog())
                ):
                    break
                w += 1
                out = self.run_window(w * self.queue.window_s)
                if out:
                    outs.append(out)
        # Overlap: the final window may still be executing.
        self._join_inflight()
        return outs, self.stats
