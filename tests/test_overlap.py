"""Overlapped async window serving: determinism, snapshot reconciliation,
lane strategies, and pool lifecycle.

The regression contract of ``EdgeServer(overlap=True)``: speculating
window k+1 while window k executes changes WHEN the host works, never
WHAT it decides.  Every test serves a deterministic trace through a
``SimulatedBackend`` (reports always carry the modelled latency, so the
closed loop feeds back identical observations in every mode) and
compares the full per-request decision tuples, not just aggregates.
"""
import numpy as np
import pytest

from repro.core import (
    POLICY_NAMES,
    Application,
    ModelProfile,
    Request,
    Worker,
    make_policy,
)
from repro.serving import (
    EdgeServer,
    ExecutorPool,
    FaultPlan,
    FaultSpec,
    LMExecutor,
    SimulatedBackend,
)

PROFILES = {
    "small": ModelProfile("small", recalls=[0.74, 0.72], latency_s=0.010,
                          load_latency_s=0.02),
    "big": ModelProfile("big", recalls=[0.93, 0.91], latency_s=0.045,
                        load_latency_s=0.08),
}
APP = Application(name="lm", models=list(PROFILES.values()), penalty="sigmoid")


def prompt_fn(req):
    return (np.arange(8, dtype=np.int32) + int(req.rid)) % 256


def make_trace(n=18):
    """Arrivals spread over ~4 scheduling windows."""
    return [Request(rid=i, app="lm", arrival_s=0.02 * i,
                    deadline_s=0.02 * i + 0.3, true_label=i % 2)
            for i in range(n)]


def serve(overlap, *, policy="LO-EDF", lane="thread", preempt=False,
          faults=None, health=False, server_cls=EdgeServer, n=18):
    backend = SimulatedBackend(PROFILES, occupancy="none")
    with server_cls(
        {"lm": APP}, make_policy(policy),
        executor=LMExecutor(backend=backend), prompt_fn=prompt_fn,
        workers=[Worker(0), Worker(1)], overlap=overlap, lane=lane,
        preempt=preempt, faults=faults, health=health,
    ) as srv:
        outs, stats = srv.run(make_trace(n))
    decisions = [
        (e.request.rid, e.model, e.worker, e.order, e.batch_id)
        for o in outs for e in o["schedule"].sorted_entries()
    ]
    return decisions, stats, srv


def assert_equivalent(a, b):
    dec_a, stats_a, _ = a
    dec_b, stats_b, _ = b
    assert dec_a == dec_b
    assert stats_a.requests == stats_b.requests
    assert stats_a.violations == stats_b.violations
    assert stats_a.mean_utility == pytest.approx(stats_b.mean_utility,
                                                 rel=1e-12, abs=1e-15)


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_overlap_matches_sync_across_policies(policy):
    assert_equivalent(serve(False, policy=policy), serve(True, policy=policy))


@pytest.mark.parametrize("preempt", [False, True])
def test_overlap_matches_sync_with_preemption(preempt):
    assert_equivalent(serve(False, preempt=preempt),
                      serve(True, preempt=preempt))


def test_overlap_matches_sync_under_faults_and_health():
    def plan():
        return FaultPlan(specs=(
            FaultSpec(kind="crash", window=0, worker=0, batch=0),
            FaultSpec(kind="transient", worker=1, count=1),
        ))
    sync = serve(False, faults=plan(), health=True)
    over = serve(True, faults=plan(), health=True)
    assert sync[1].failed_batches > 0  # the scenario actually fired
    assert_equivalent(sync, over)


class SpyServer(EdgeServer):
    """Counts schedules taken against the REAL committed state — in
    overlap mode that is the first window (nothing inflight yet) plus
    every window whose speculation was invalidated at reconcile."""

    def _schedule_requests(self, requests, now, state):
        if state is self.state:
            self.real_schedules = getattr(self, "real_schedules", 0) + 1
        return super()._schedule_requests(requests, now, state)


def test_speculation_commits_without_rescheduling_on_quiet_windows():
    # No faults, no preemption, no health: every speculative schedule
    # must survive reconciliation, so the only schedule against the real
    # state is window 0 (before anything is inflight).
    dec, stats, srv = serve(True, server_cls=SpyServer)
    assert stats.windows > 2
    assert srv.real_schedules == 1
    assert stats.overlap_saved_s >= 0.0


def test_fault_withdrawal_invalidates_speculation():
    # Window k crashes a batch -> its retry becomes due while window
    # k+1's speculative schedule is already built.  The retry lands
    # between k's execution and k+1's commit, so the reconcile step must
    # throw the speculation away and re-schedule against the real state
    # — and the result must still match the synchronous loop exactly.
    def plan():
        return FaultPlan(specs=(
            FaultSpec(kind="crash", window=0, worker=0, batch=0),))
    sync = serve(False, faults=plan(), health=True)
    over = serve(True, faults=plan(), health=True, server_cls=SpyServer)
    assert sync[1].retries > 0
    assert over[2].real_schedules >= 2  # window 0 + >=1 invalidation
    assert_equivalent(sync, over)


@pytest.mark.parametrize("lane", ["serial", "thread"])
def test_lane_parity(lane):
    assert_equivalent(serve(False, lane="thread"), serve(True, lane=lane))


def test_process_lane_parity():
    # Spawned workers hold their own backend instance; schedules ship as
    # plain arrays over pipes.  Decisions must match the thread lane.
    assert_equivalent(serve(False, lane="thread", n=8),
                      serve(True, lane="process", n=8))


def test_unknown_lane_rejected():
    backend = SimulatedBackend(PROFILES, occupancy="none")
    with pytest.raises(ValueError, match="lane"):
        ExecutorPool([Worker(0)], backend_factory=lambda: backend.spawn(),
                     lane="rocket")


def test_process_lane_refused_on_an_accelerator(monkeypatch):
    # A chip belongs to one process: with JAX on a TPU the process lane
    # raises before it spawns a child that would need the device.
    import multiprocessing

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    backend = SimulatedBackend(PROFILES, occupancy="none")
    before = set(multiprocessing.active_children())
    with pytest.raises(RuntimeError, match="one process per chip"):
        ExecutorPool([Worker(0)], backend_factory=lambda: backend.spawn(),
                     lane="process")
    assert set(multiprocessing.active_children()) <= before


def test_executor_pool_lifecycle():
    backend = SimulatedBackend(PROFILES, occupancy="none")
    pool = ExecutorPool([Worker(0), Worker(1)],
                        backend_factory=lambda: backend.spawn())
    with pool:
        pass
    pool.close()  # idempotent


def test_server_close_idempotent_and_reusable_stats():
    dec, stats, srv = serve(True)
    srv.close()
    srv.close()
    assert stats.requests == len(make_trace())
