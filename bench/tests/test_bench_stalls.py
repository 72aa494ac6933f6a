"""The stall watch: a gap in its own ticks is the whole process held; the
main thread at one place within one step of the loop is the main thread
held there; counters are differenced across each stall."""
import time

from bench import stalls


def _watch(samples):
    w = stalls.Watch(period_s=0.02, gap_s=0.1, place_s=0.3)
    w.samples = [(t, where, {"c": c}) for t, where, c in samples]
    return w


def test_a_gap_in_the_ticks_is_a_process_stall():
    w = _watch([(0.00, (0, "a"), 0), (0.02, (0, "b"), 1), (0.52, (1, "c"), 9), (0.54, (1, "d"), 9)])
    (s,) = w.stalls()
    assert s["kind"] == "process held" and abs(s["s"] - 0.5) < 1e-9
    assert s["where"] == "b" and s["delta"] == {"c": 8}


def test_one_place_in_one_step_is_a_main_stall():
    same = [(0.02 * i, (3, "x"), i) for i in range(30)]  # 0.58 s at one place
    w = _watch(same + [(0.60, (4, "x"), 40)])
    (s,) = w.stalls()
    assert s["kind"] == "main at one place" and s["where"] == "x" and s["delta"] == {"c": 29}


def test_a_new_loop_step_at_the_same_place_is_no_stall():
    w = _watch([(0.02 * i, (i // 5, "sleep"), i) for i in range(50)])
    assert w.stalls() == []


def test_the_thread_samples_and_stops():
    w = stalls.Watch(period_s=0.005).start()
    time.sleep(0.05)
    w.stop()
    assert not w._thread.is_alive()
    assert len(w.samples) >= 3 and "major_faults" in w.totals()
