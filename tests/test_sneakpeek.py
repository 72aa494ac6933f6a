"""SneakPeek data-awareness tests: estimation quality, short-circuit, splitting."""
import numpy as np
import pytest

from repro.core import (
    ConfusionSneakPeek,
    KNNSneakPeek,
    attach_sneakpeek,
    expected_accuracy,
    make_policy,
    run_window,
)
from repro.core.types import Request
from repro.data.applications import (
    APP_SPECS,
    build_benchmark_suite,
    make_application,
    make_dataset,
    make_requests,
    make_sneakpeek,
)


def _fresh(reqs):
    return [Request(r.rid, r.app, r.arrival_s, r.deadline_s, r.features, r.true_label) for r in reqs]


# ---------------------------------------------------------------- estimation


@pytest.mark.parametrize("app_name", list(APP_SPECS))
def test_sneakpeek_beats_profiled_estimation(app_name):
    """Fig. 6: posterior-sharpened accuracy has lower error than profiled."""
    spec = APP_SPECS[app_name]
    app = make_application(spec)
    reqs = make_requests([spec], per_app=150, seed=3)
    sp = make_sneakpeek(spec, k=5, backend="numpy")
    attach_sneakpeek(reqs, {app_name: app}, {app_name: sp})
    err_prof, err_sp = [], []
    for r in reqs:
        for m in app.models:
            oracle = m.recalls[r.true_label]
            err_prof.append(abs(m.profiled_accuracy() - oracle))
            err_sp.append(abs(expected_accuracy(m.recalls, r.theta) - oracle))
    assert np.mean(err_sp) < np.mean(err_prof)


def test_k5_beats_k1():
    """Fig. 6: more neighbors -> better evidence."""
    spec = APP_SPECS["fall_detection"]
    app = make_application(spec)
    reqs = make_requests([spec], per_app=200, seed=5)
    errs = {}
    for k in (1, 5):
        rs = _fresh(reqs)
        sp = make_sneakpeek(spec, k=k, backend="numpy")
        attach_sneakpeek(rs, {spec.name: app}, {spec.name: sp})
        errs[k] = np.mean([
            abs(expected_accuracy(m.recalls, r.theta) - m.recalls[r.true_label])
            for r in rs for m in app.models
        ])
    assert errs[5] < errs[1]


def test_decision_rule_amplifies_wrong_predictions():
    """§IV-B mechanism: one-hot decision-rule evidence commits the full
    weight to a single class, so a WRONG prediction produces a more
    confidently-wrong posterior than split k-NN votes do."""
    from repro.core.dirichlet import jeffreys_prior, posterior_mean

    prior = jeffreys_prior(2)
    # k-NN saw 3 votes for class 1, 2 for class 0 (uncertain, correct=0)
    knn_theta = posterior_mean(prior, np.array([2.0, 3.0]))
    # decision rule turns the same majority into a 5-0 point mass
    dr_theta = posterior_mean(prior, np.array([0.0, 5.0]))
    # both lean class 1, but the decision rule is further from truth (class 0)
    assert dr_theta[0] < knn_theta[0] < 0.5


def test_confusion_sneakpeek_accuracy_controls_quality():
    """Fig. 8 mechanism: higher synthetic SneakPeek accuracy -> lower error."""
    spec = APP_SPECS["voice_commands"]
    app = make_application(spec)
    reqs = make_requests([spec], per_app=200, seed=11)
    errs = []
    for acc in (0.2, 0.6, 0.95):
        rs = _fresh(reqs)
        sp = ConfusionSneakPeek(spec.num_classes, acc, k=5, seed=1)
        attach_sneakpeek(rs, {spec.name: app}, {spec.name: sp})
        errs.append(np.mean([
            abs(expected_accuracy(m.recalls, r.theta) - m.recalls[r.true_label])
            for r in rs for m in app.models
        ]))
    assert errs[2] < errs[1] < errs[0]


def test_knn_votes_scatter_matches_bincount_loop():
    """Regression: the np.add.at scatter in KNNSneakPeek._votes counts
    exactly what the per-row bincount loop counted."""
    spec = APP_SPECS["heart_monitoring"]
    rng = np.random.default_rng(3)
    x, y = make_dataset(spec, 300, rng)
    q, _ = make_dataset(spec, 64, rng)
    for k in (1, 5, 11):
        sp = KNNSneakPeek(x, y, spec.num_classes, k=k, backend="numpy", seed=1)
        votes = sp._votes(q)
        assert votes.shape == (64, spec.num_classes)
        np.testing.assert_allclose(votes.sum(axis=1), min(k, len(sp.train_x)))
        # reference: per-row exact search + bincount
        d2 = ((q[:, None, :] - sp.train_x[None, :, :]) ** 2).sum(-1)
        kk = min(k, sp.train_x.shape[0])
        nn = np.argpartition(d2, kth=kk - 1, axis=1)[:, :kk]
        ref = np.stack([
            np.bincount(sp.train_y[nn[b]], minlength=spec.num_classes)
            for b in range(q.shape[0])
        ])
        np.testing.assert_array_equal(votes, ref)


def test_confusion_evidence_batch_matches_sequential_draws():
    """One vectorized multinomial draw == per-request draws in batch
    order under the same seed (call-order independence satellite)."""
    labels = [0, 3, 1, 1, 5, 2, 0, 4]
    sp_a = ConfusionSneakPeek(6, accuracy=0.8, k=5, seed=123)
    seq = np.stack([sp_a.evidence(None, t) for t in labels])
    sp_b = ConfusionSneakPeek(6, accuracy=0.8, k=5, seed=123)
    bat = sp_b.evidence_batch(np.zeros((len(labels), 4)), labels)
    np.testing.assert_array_equal(seq, bat)
    np.testing.assert_allclose(bat.sum(axis=1), 5.0)
    with pytest.raises(ValueError):
        sp_b.evidence_batch(np.zeros((2, 4)), [0, None])
    with pytest.raises(ValueError):
        sp_b.evidence_batch(np.zeros((2, 4)))


def test_ingest_window_matches_per_request_attach():
    """The batched ingest fills the same evidence/theta the per-request
    loop filled (KNN evidence is deterministic)."""
    from repro.core.dirichlet import posterior_mean

    apps, sneaks = build_benchmark_suite(backend="numpy")
    reqs = make_requests(list(APP_SPECS.values()), per_app=5, seed=9)
    attach_sneakpeek(reqs, apps, sneaks)
    for r in reqs:
        sp = sneaks[r.app]
        y = sp.evidence(r.features, r.true_label)
        np.testing.assert_array_equal(r.evidence, y)
        np.testing.assert_array_equal(r.theta, posterior_mean(apps[r.app].prior, y))


def test_knn_jax_backend_matches_numpy():
    spec = APP_SPECS["fall_detection"]
    rng = np.random.default_rng(0)
    x, y = make_dataset(spec, 200, rng)
    q, _ = make_dataset(spec, 16, rng)
    sp_np = KNNSneakPeek(x, y, spec.num_classes, k=5, backend="numpy", seed=1)
    sp_jx = KNNSneakPeek(x, y, spec.num_classes, k=5, backend="jax", seed=1)
    v_np = sp_np.evidence_batch(q)
    v_jx = sp_jx.evidence_batch(q)
    np.testing.assert_array_equal(v_np, v_jx)


# ---------------------------------------------------------------- short-circuit


def test_short_circuit_rescues_tight_deadlines():
    """With impossible deadlines, SneakPeek (zero-latency) answers win."""
    apps, sneaks = build_benchmark_suite(backend="numpy")
    reqs = make_requests(list(APP_SPECS.values()), per_app=4, mean_deadline_s=0.015, seed=2)
    pol = make_policy("SneakPeek")
    wr = run_window(pol, _fresh(reqs), apps, 0.1, sneakpeeks=sneaks, short_circuit=True)
    used = {e.model for e in wr.schedule.entries}
    assert any(m.endswith(":short_circuit") for m in used)
    wr_no = run_window(pol, _fresh(reqs), apps, 0.1, sneakpeeks=sneaks, short_circuit=False)
    assert wr.result.mean_utility >= wr_no.result.mean_utility - 1e-9


def test_loose_deadlines_pick_max_estimated_accuracy():
    """With loose deadlines the grouped selector is pure accuracy-max: any
    chosen variant (short-circuit included) must estimate at least as
    accurate as the short-circuit candidate for that group."""
    from repro.core.evaluation import estimate_accuracy

    apps, sneaks = build_benchmark_suite(backend="numpy")
    reqs = make_requests(list(APP_SPECS.values()), per_app=2, mean_deadline_s=5.0, seed=2)
    wr = run_window(make_policy("SneakPeek"), _fresh(reqs), apps, 0.1,
                    sneakpeeks=sneaks, short_circuit=True)
    # reconstruct the effective apps (with the SC variant appended)
    from repro.core.scheduler import schedule_window

    reqs2 = _fresh(reqs)
    _, eff_apps = schedule_window(make_policy("SneakPeek"), reqs2, apps, 0.1,
                                  sneakpeeks=sneaks, short_circuit=True)
    by_rid = {r.rid: r for r in reqs2}
    for e in wr.schedule.entries:
        app = eff_apps[e.request.app]
        sc = [m for m in app.models if m.is_short_circuit][0]
        chosen = app.model(e.model)
        r = by_rid[e.request.rid]
        acc_chosen = estimate_accuracy(r, app, chosen, "sharpened")
        acc_sc = estimate_accuracy(r, app, sc, "sharpened")
        assert acc_chosen >= acc_sc - 0.15  # group-mean selection tolerance


def test_knn_kernel_error_propagates_from_auto_backend(monkeypatch):
    """A failing k-NN kernel surfaces; it never comes back as numpy votes."""
    from repro.kernels.knn import ops as knn_ops

    def broken(*args, **kwargs):
        raise RuntimeError("kernel failed to lower")

    monkeypatch.setattr(knn_ops, "knn_class_votes", broken)
    spec = APP_SPECS["fall_detection"]
    x, y = make_dataset(spec, 100, np.random.default_rng(0))
    sp = KNNSneakPeek(x, y, spec.num_classes, k=5, backend="auto", seed=1)
    with pytest.raises(RuntimeError, match="kernel failed to lower"):
        sp._votes(x[:4])


def test_knn_unknown_backend_is_refused():
    spec = APP_SPECS["fall_detection"]
    x, y = make_dataset(spec, 50, np.random.default_rng(0))
    with pytest.raises(ValueError, match="unknown backend"):
        KNNSneakPeek(x, y, spec.num_classes, backend="faiss")
