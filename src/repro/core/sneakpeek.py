"""SneakPeek models (paper §IV, Definitions 4.1.1-4.1.2).

A SneakPeek model maps a request's raw features to *multinomial evidence*
``y`` over the class labels; the Dirichlet posterior mean (Eq. 11) is the
SneakPeek probability vector used to sharpen Eq. 9 accuracies.

Implementations:

  * ``KNNSneakPeek`` — the paper's primary mechanism: k nearest neighbors
    in the training set vote (e.g. k=5, two "no fall" + three "fall" ->
    y = <2, 3>).  The distance/top-k computation runs through the Pallas
    TPU kernel (``repro.kernels.knn``), or through an exact numpy search
    when ``backend="numpy"`` is asked for (the paper uses Faiss on CPU).
  * ``DecisionRuleSneakPeek`` — the "low-information" one-hot alternative
    discussed in §IV-B.
  * ``ConfusionSneakPeek`` — the synthetic model of Fig. 8: given a target
    accuracy, evidence is drawn from the true-label row of a synthetic
    confusion matrix (used to ask "how accurate must SneakPeek models be?").

Each SneakPeek model can also act as a *short-circuit* variant (§V-C1):
``predict`` returns a label directly, and ``profile`` wraps it in a
zero-latency ModelProfile whose accuracy stays profiled.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro import tracing
from repro.core.accuracy import ModelProfile, confusion_with_accuracy, recalls_from_confusion
from repro.core.dirichlet import posterior_mean_batch

__all__ = [
    "SneakPeekModel",
    "KNNSneakPeek",
    "DecisionRuleSneakPeek",
    "ConfusionSneakPeek",
    "ingest_window",
    "attach_sneakpeek",
]


class SneakPeekModel:
    """Interface: evidence(features) -> multinomial counts over classes."""

    num_classes: int
    name: str = "sneakpeek"

    def evidence(self, features: np.ndarray, true_label: int | None = None) -> np.ndarray:
        """Multinomial evidence counts y for one request (Eq. 11 input)."""
        raise NotImplementedError

    def evidence_batch(
        self, features: np.ndarray, true_labels: Sequence[int | None] | None = None
    ) -> np.ndarray:
        """(B, num_classes) evidence for a whole window's feature batch.

        The default loops over ``evidence`` row by row (same draws, same
        order); implementations override with a genuinely batched compute
        (k-NN kernel tiles, one vectorized multinomial draw, ...).
        """
        feats = np.atleast_2d(np.asarray(features))
        labels = true_labels if true_labels is not None else [None] * len(feats)
        return np.stack([self.evidence(f, t) for f, t in zip(feats, labels)])

    def predict(self, features: np.ndarray, true_label: int | None = None) -> int:
        """Short-circuit prediction: majority class of the evidence."""
        return int(np.argmax(self.evidence(features, true_label)))

    def measured_recalls(self) -> np.ndarray:
        """Per-class recall of ``predict`` measured on held-out data.

        Subclasses override with their own measurement; default assumes
        uniform moderate quality (used only when no holdout exists).
        """
        return np.full(self.num_classes, 0.7)

    def profile(self, latency_s: float = 0.0) -> ModelProfile:
        """Wrap as a zero-latency short-circuit candidate (§V-C1)."""
        return ModelProfile(
            name=f"{self.name}:short_circuit",
            recalls=self.measured_recalls(),
            latency_s=latency_s,
            load_latency_s=0.0,
            is_short_circuit=True,
        )


class KNNSneakPeek(SneakPeekModel):
    """k-NN vote evidence against the (sub-sampled) training set.

    ``backend`` "auto" and "jax" run the Pallas k-NN kernel (a kernel
    error propagates); "numpy" runs the exact search on the host.
    """

    BACKENDS = ("auto", "jax", "numpy")

    def __init__(
        self,
        train_x: np.ndarray,
        train_y: np.ndarray,
        num_classes: int,
        k: int = 5,
        name: str = "knn",
        backend: str = "auto",
        holdout_frac: float = 0.2,
        seed: int = 0,
    ):
        train_x = np.asarray(train_x, dtype=np.float32)
        train_y = np.asarray(train_y, dtype=np.int32)
        if train_x.ndim != 2 or train_y.ndim != 1 or len(train_x) != len(train_y):
            raise ValueError("train_x must be (N, D), train_y (N,)")
        if k < 1:
            raise ValueError("k must be >= 1")
        if backend not in self.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; expected one of {self.BACKENDS}")
        self.num_classes = int(num_classes)
        self.k = int(k)
        self.name = name
        self.backend = backend
        # Hold out a slice for measuring the short-circuit recalls.
        rng = np.random.default_rng(seed)
        n = len(train_x)
        perm = rng.permutation(n)
        n_hold = max(self.num_classes, int(n * holdout_frac))
        self._hold_x, self._hold_y = train_x[perm[:n_hold]], train_y[perm[:n_hold]]
        self.train_x, self.train_y = train_x[perm[n_hold:]], train_y[perm[n_hold:]]
        self._recalls_cache: np.ndarray | None = None

    # -- evidence ----------------------------------------------------------
    def _votes(self, queries: np.ndarray) -> np.ndarray:
        """(B, num_classes) vote counts for a batch of queries."""
        queries = np.atleast_2d(np.asarray(queries, dtype=np.float32))
        with tracing.span("ingest.knn", model=self.name, rows=queries.shape[0]):
            return self._search(queries)

    def _search(self, queries: np.ndarray) -> np.ndarray:
        if self.backend != "numpy":
            from repro.kernels.knn import ops as knn_ops

            return np.asarray(
                knn_ops.knn_class_votes(
                    queries, self.train_x, self.train_y, self.k, self.num_classes
                )
            )
        # numpy exact search (what the paper's Faiss-on-CPU computes)
        d2 = (
            (queries**2).sum(1)[:, None]
            - 2.0 * queries @ self.train_x.T
            + (self.train_x**2).sum(1)[None, :]
        )
        k = min(self.k, self.train_x.shape[0])
        nn = np.argpartition(d2, kth=k - 1, axis=1)[:, :k]
        # One scatter-add over the (row, neighbor-label) pairs replaces the
        # per-row bincount loop (identical counts, see tests/test_sneakpeek).
        votes = np.zeros((queries.shape[0], self.num_classes))
        rows = np.repeat(np.arange(queries.shape[0]), k)
        np.add.at(votes, (rows, self.train_y[nn].ravel()), 1.0)
        return votes

    def evidence(self, features: np.ndarray, true_label: int | None = None) -> np.ndarray:
        """k-NN vote counts for one request's features."""
        return self._votes(features)[0]

    def evidence_batch(
        self, features: np.ndarray, true_labels: Sequence[int | None] | None = None
    ) -> np.ndarray:
        """One batched k-NN vote tile for the whole window."""
        return self._votes(features)

    def measured_recalls(self) -> np.ndarray:
        """Held-out per-class recall of the k-NN majority vote (cached)."""
        if self._recalls_cache is None:
            votes = self._votes(self._hold_x)
            preds = votes.argmax(axis=1)
            rec = np.zeros(self.num_classes)
            for c in range(self.num_classes):
                mask = self._hold_y == c
                rec[c] = (preds[mask] == c).mean() if mask.any() else 0.5
            self._recalls_cache = rec
        return self._recalls_cache


class DecisionRuleSneakPeek(SneakPeekModel):
    """One-hot evidence from an arbitrary classifier's decision rule (§IV-B).

    Low-information update: the full evidence weight k lands on a single
    predicted class, amplifying errors when the prediction is wrong.
    """

    def __init__(self, base: SneakPeekModel, weight: int = 5, name: str | None = None):
        self.base = base
        self.weight = int(weight)
        self.num_classes = base.num_classes
        self.name = name or f"{base.name}:decision_rule"

    def evidence(self, features: np.ndarray, true_label: int | None = None) -> np.ndarray:
        """One-hot evidence: full weight on the base model's prediction."""
        pred = self.base.predict(features, true_label)
        y = np.zeros(self.num_classes)
        y[pred] = self.weight
        return y

    def measured_recalls(self) -> np.ndarray:
        """Recalls of the underlying base model (the rule adds no skill)."""
        return self.base.measured_recalls()


class ConfusionSneakPeek(SneakPeekModel):
    """Synthetic SneakPeek model with controlled accuracy (paper Fig. 8).

    Evidence for a data point with true label t is a multinomial draw of k
    votes from row t of a confusion matrix with the requested accuracy
    (errors uniform over the other classes).
    """

    def __init__(
        self,
        num_classes: int,
        accuracy: float,
        k: int = 5,
        seed: int = 0,
        name: str | None = None,
    ):
        self.num_classes = int(num_classes)
        self.accuracy = float(accuracy)
        self.k = int(k)
        self.rng = np.random.default_rng(seed)
        self.name = name or f"confusion@{accuracy:.2f}"
        z = confusion_with_accuracy(num_classes, accuracy)
        self._rows = z / z.sum(axis=1, keepdims=True)

    def evidence(self, features: np.ndarray, true_label: int | None = None) -> np.ndarray:
        """k votes drawn from the true label's confusion-matrix row."""
        if true_label is None:
            raise ValueError("ConfusionSneakPeek requires the true label")
        return self.rng.multinomial(self.k, self._rows[true_label]).astype(np.float64)

    def evidence_batch(
        self, features: np.ndarray, true_labels: Sequence[int | None] | None = None
    ) -> np.ndarray:
        """One vectorized multinomial draw for the whole batch.

        numpy's Generator draws batched multinomials row by row from the
        same stream, so this consumes the RNG exactly like ``evidence``
        called once per request in batch order — the batched ingest and
        the scalar path agree under a fixed seed.
        """
        if true_labels is None or any(t is None for t in true_labels):
            raise ValueError("ConfusionSneakPeek requires the true labels")
        labels = np.asarray(list(true_labels), dtype=np.int64)
        return self.rng.multinomial(self.k, self._rows[labels]).astype(np.float64)

    def measured_recalls(self) -> np.ndarray:
        """Per-class recall of the synthetic confusion matrix."""
        return recalls_from_confusion(self._rows)


def ingest_window(
    requests,
    apps,
    sneakpeeks: dict[str, SneakPeekModel],
) -> None:
    """Batched SneakPeek stage: fill request.evidence and request.theta.

    One SneakPeek inference per request updates the accuracy estimate for
    *every* variant of its application (the paper's single-inference
    amortization, §IV-B).  The window is partitioned per application and
    each partition runs as ONE batched evidence compute (k-NN kernel tile
    or vectorized multinomial) followed by ONE batched Dirichlet update
    (Eq. 11), preserving within-app request order so stochastic evidence
    models draw exactly as the per-request loop would.  Requests of
    applications without a SneakPeek model are left untouched (they fall
    back to profiled accuracy).  Requests that already carry evidence are
    left untouched: the SneakPeek draw happens ONCE per request, so a
    request re-admitted to a later window after preemption keeps the
    posterior attached at first ingest instead of redrawing (stochastic
    evidence models would otherwise fork the stream).
    """
    by_app: dict[str, list[int]] = {}
    for i, r in enumerate(requests):
        if r.evidence is None and sneakpeeks.get(r.app) is not None:
            by_app.setdefault(r.app, []).append(i)
    for app_name, idxs in by_app.items():
        sp = sneakpeeks[app_name]
        if any(requests[i].features is None for i in idxs):
            # Feature-free evidence models (ConfusionSneakPeek) ignore this;
            # feature-based ones fail on the shape mismatch, as they should.
            feats = np.zeros((len(idxs), 0), dtype=np.float32)
        else:
            # Caller precision is preserved: models that want float32
            # (the k-NN kernels) cast internally.
            feats = np.stack([np.asarray(requests[i].features) for i in idxs])
        labels = [requests[i].true_label for i in idxs]
        evidence = np.asarray(sp.evidence_batch(feats, labels), dtype=np.float64)
        theta = posterior_mean_batch(apps[app_name].prior, evidence)
        for row, i in enumerate(idxs):
            requests[i].evidence = evidence[row]
            requests[i].theta = theta[row]


def attach_sneakpeek(
    requests,
    apps,
    sneakpeeks: dict[str, SneakPeekModel],
) -> None:
    """Run the SneakPeek stage (delegates to the batched ``ingest_window``)."""
    ingest_window(requests, apps, sneakpeeks)
