"""Property tests: the array-encoded LRU residency rule must agree with
``core/residency.py``'s host eviction rule on arbitrary swap sequences.

``touch_lru_array`` (numpy slot vectors — the encoding both the
multi-worker fast path and the compiled pipeline selectors thread) is
checked against ``WorkerTimeline._touch``/``evict_lru`` (name-keyed host
lists) on random sequences of model loads, random sizes and capacities —
including the oversize-model-resides-alone case — plus the single-slot
(capacity ``None``) encoding and the lossless ``StreamingState``
to/from-array round trip."""
import numpy as np
import pytest

try:  # optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; example tests still run
    from _hypothesis_stub import given, settings, st

from repro.core.accuracy import ModelProfile
from repro.core.evaluation import WorkerTimeline
from repro.core.residency import evict_lru, single_slot_encoding, touch_lru_array
from repro.core.streaming import StreamingState


def _profile(name: str, size: int) -> ModelProfile:
    return ModelProfile(
        name=name,
        latency_s=0.01,
        recalls=np.array([0.9, 0.9]),
        load_latency_s=0.005,
        memory_bytes=size,
    )


def _replay(sizes, capacity, sequence):
    """Run one load sequence through both encodings; assert equal resident
    sets (same names, same LRU order) after every step."""
    n = len(sizes)
    profiles = [_profile(f"m{i}", sizes[i]) for i in range(n)]
    tl = WorkerTimeline(now=0.0, memory_capacity_bytes=capacity)
    res = np.full(n, -1, dtype=np.int64)
    if capacity is None:
        arr_sizes, cap = single_slot_encoding(n)
    else:
        arr_sizes, cap = np.asarray(sizes, dtype=np.float64), float(capacity)
    for gid in sequence:
        was_host = tl._is_resident(f"m{gid}")
        swap = tl._touch(profiles[gid])
        res, was_arr = touch_lru_array(res, gid, arr_sizes, cap)
        assert was_arr == was_host == (swap == 0.0)
        host_names = list(tl._resident)
        arr_names = [f"m{g}" for g in res if g >= 0]
        assert arr_names == host_names, (sizes, capacity, sequence)
        # Padding stays packed at the tail.
        tail = res[len(arr_names):]
        assert (tail == -1).all()
    return tl, res


@settings(max_examples=200, deadline=None)
@given(
    sizes=st.lists(st.integers(min_value=0, max_value=100), min_size=1, max_size=6),
    capacity=st.integers(min_value=0, max_value=250),
    seq=st.lists(st.integers(min_value=0, max_value=5), min_size=1, max_size=30),
)
def test_touch_lru_array_matches_host_rule(sizes, capacity, seq):
    sequence = [g % len(sizes) for g in seq]
    _replay(sizes, capacity, sequence)


@settings(max_examples=100, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=6),
    seq=st.lists(st.integers(min_value=0, max_value=5), min_size=1, max_size=20),
)
def test_touch_lru_array_single_slot_encoding(n, seq):
    """capacity=None (the paper's conservative single-slot model) folds
    into the same rule via unit sizes + zero capacity."""
    sequence = [g % n for g in seq]
    tl, res = _replay([10] * n, None, sequence)
    assert len(tl._resident) == 1  # single-slot: exactly the last load


def test_oversize_model_resides_alone():
    """Regression (shared rule): a model larger than capacity evicts
    everything else but is NEVER evicted itself — in both encodings."""
    sizes = [60, 60, 500]
    tl, res = _replay(sizes, 100, [0, 1, 2, 2, 0])
    # After loading m2 (oversize): resides alone; re-touch keeps it; then
    # loading m0 evicts the over-budget m2.
    assert list(tl._resident) == ["m0"]
    # And explicitly through evict_lru:
    resident = ["m0", "m1", "huge"]
    evicted = evict_lru(
        resident, {"m0": 60, "m1": 60, "huge": 500}, 100, protect="huge"
    )
    assert resident == ["huge"] and evicted == ["m0", "m1"]


def test_touch_example_eviction_order():
    """Example-based twin of the property test (runs without hypothesis):
    oldest-first eviction, protect skipped, MRU reorder on a resident
    touch."""
    sizes = np.array([50.0, 40.0, 30.0])
    res = np.full(3, -1, dtype=np.int64)
    res, was = touch_lru_array(res, 0, sizes, 100.0)
    assert not was and list(res) == [0, -1, -1]
    res, was = touch_lru_array(res, 1, sizes, 100.0)
    assert not was and list(res) == [0, 1, -1]
    res, was = touch_lru_array(res, 0, sizes, 100.0)  # MRU reorder
    assert was and list(res) == [1, 0, -1]
    res, was = touch_lru_array(res, 2, sizes, 100.0)  # evicts oldest (1)
    assert not was and list(res) == [0, 2, -1]


def test_streaming_state_array_round_trip():
    """StreamingState.to_arrays / from_arrays is lossless: busy-until
    times, LRU residency order, and registered sizes all survive."""
    state = StreamingState(
        num_workers=2, now=0.25, memory_capacity_bytes=1000, worker_ids=[3, 7]
    )
    p_a, p_b = _profile("a", 600), _profile("b", 300)
    state.timeline(3).run_batch(p_a, 2)
    state.timeline(3).run_batch(p_b, 1)
    state.timeline(7).run_batch(p_b, 4)
    gids = {"a": 0, "b": 1, "never-used": 2}
    t, res, reg = state.to_arrays(gids, wids=[3, 7])
    assert t.shape == (2,) and res.shape == (2, 3) and reg.shape == (2, 3)
    back = StreamingState.from_arrays(
        t, res, reg, ["a", "b", "never-used"],
        memory_capacity_bytes=1000, wids=[3, 7],
    )
    for w in (3, 7):
        a, b = state.timeline(w), back.timeline(w)
        assert a.t == b.t
        assert list(a._resident) == list(b._resident)
        assert a._profiles == b._profiles
    assert back.capacity == state.capacity


@settings(max_examples=50, deadline=None)
@given(
    seq=st.lists(
        st.tuples(st.integers(min_value=0, max_value=1),
                  st.integers(min_value=0, max_value=3)),
        min_size=0, max_size=12,
    ),
    cap=st.one_of(st.none(), st.integers(min_value=0, max_value=2000)),
)
def test_streaming_state_round_trip_property(seq, cap):
    """Round trip after arbitrary (worker, model) load sequences."""
    profiles = [_profile(f"m{i}", 100 * (i + 1)) for i in range(4)]
    state = StreamingState(num_workers=2, memory_capacity_bytes=cap)
    for wid, mi in seq:
        state.timeline(wid).run_batch(profiles[mi], 1)
    gids = {f"m{i}": i for i in range(4)}
    t, res, reg = state.to_arrays(gids)
    back = StreamingState.from_arrays(
        t, res, reg, [f"m{i}" for i in range(4)], memory_capacity_bytes=cap
    )
    for w in (0, 1):
        assert state.timeline(w).t == back.timeline(w).t
        assert state.timeline(w)._resident == back.timeline(w)._resident
        assert state.timeline(w)._profiles == back.timeline(w)._profiles


def test_compiled_touch_matches_numpy_form():
    """The jitted ``pipeline._touch_residency`` is the same rule as the
    numpy ``touch_lru_array`` on random sequences (including oversize)."""
    jax = pytest.importorskip("jax")

    from repro.core.pipeline import _touch_residency

    rng = np.random.default_rng(0)
    with jax.enable_x64(True):
        jit_touch = jax.jit(_touch_residency)
        for trial in range(20):
            n = int(rng.integers(1, 6))
            sizes = rng.integers(0, 100, size=n).astype(np.float64)
            cap = float(rng.integers(0, 250))
            res_np = np.full(n, -1, dtype=np.int64)
            res_j = np.full(n, -1, dtype=np.int64)
            for _ in range(15):
                gid = int(rng.integers(0, n))
                res_np, was_np = touch_lru_array(res_np, gid, sizes, cap)
                out, was_j = jit_touch(res_j, gid, sizes, cap)
                res_j = np.asarray(out)
                assert bool(was_j) == was_np
                np.testing.assert_array_equal(res_j, res_np)
