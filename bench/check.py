"""The comparison that decides ``correct``.

* ingest: every k-NN vote row the timed window produced, against an exact
  float64 search on the host over the same training rows.  A row whose k-th
  and (k+1)-th reference distances lie within float32 rounding of each other
  is a tie, where either neighbour is right; every other differing row is
  wrong.  Limit: 0 wrong rows.
* scheduler: every window the program closed, replayed through the plain
  scheduler of ``bench.scheduler_ref`` on the same requests, the same
  checked votes and the same variant profiles, with its own worker queue
  carried from window to window; the number is the count of requests whose
  (variant, order, batch) differ.  Limit: 0 (``harness.replay_decisions``).
* execution: a sample, drawn from the seed, of the requests the window
  finished, per served model.  The reference (``bench.reference``) reads
  each prompt with its served tokens; the number compared is the widest gap
  by which a served token's reference logit lies below the reference's best
  at that position, in units of the standard deviation of the reference
  logits there.  Greedy decoding in the program's bf16 puts that gap near
  0; each architecture's limit (``GAP_LIMIT`` of its module in
  ``bench/archs/``) sits between the program's readings and the float8
  control's (see ``PERF.md``).
"""
from __future__ import annotations

import numpy as np

SAMPLE_PER_MODEL = 32


def knn_reference(queries, train_x, train_y, k: int, num_classes: int):
    """(votes, tie) of an exact float64 k-NN search, in the kernel's
    distance convention |x|^2 - 2 q.x."""
    q = np.asarray(queries, np.float64)
    x = np.asarray(train_x, np.float64)
    d2 = (x * x).sum(1)[None, :] - 2.0 * q @ x.T
    order = np.argsort(d2, axis=1, kind="stable")
    nn = order[:, :k]
    votes = np.zeros((len(q), num_classes))
    np.add.at(votes, (np.repeat(np.arange(len(q)), k), np.asarray(train_y)[nn].ravel()), 1.0)
    dk = np.take_along_axis(d2, order[:, k - 1:k + 1], axis=1)
    # float32 rounding of the kernel's distance: D ulps of its largest term
    eps = np.finfo(np.float32).eps
    xmax = np.sqrt((x * x).sum(1).max())
    tol = 2 * q.shape[1] * eps * (xmax ** 2 + 2 * np.sqrt((q * q).sum(1)) * xmax)
    return votes, (dk[:, 1] - dk[:, 0]) <= tol


def knn_wrong_rows(calls, train_x, train_y, k: int, num_classes: int) -> tuple[int, int, int]:
    """(rows, wrong rows, tie rows) over every recorded ``evidence_batch``."""
    rows = wrong = ties = 0
    for queries, votes in calls:
        ref, tie = knn_reference(queries, train_x, train_y, k, num_classes)
        bad = np.any(np.asarray(votes) != ref, axis=1)
        rows += len(ref)
        ties += int((bad & tie).sum())
        wrong += int((bad & ~tie).sum())
    return rows, wrong, ties


def sample_rids(rids_by_model: dict, seed: int, n: int = SAMPLE_PER_MODEL) -> dict:
    """Up to ``n`` finished requests per served model, drawn from the seed."""
    rng = np.random.default_rng([int(seed), 3])
    out = {}
    for model, rids in sorted(rids_by_model.items()):
        rids = sorted(rids)
        take = min(n, len(rids))
        out[model] = sorted(rng.choice(rids, size=take, replace=False).tolist()) if take else []
    return out


def token_gaps(ref_logits, chosen) -> np.ndarray:
    """Per position: (reference best - reference logit of ``chosen``) / std."""
    ref = np.asarray(ref_logits, np.float64)
    got = np.take_along_axis(ref, np.asarray(chosen)[..., None], -1)[..., 0]
    return (ref.max(-1) - got) / ref.std(-1)


def served_gap(dims, weights, prompts, served, n_pad: int, quant: bool = False) -> float:
    """Widest normalized gap over the sampled requests of one model.

    ``prompts`` (R, P) and ``served`` (R, T) token ids; the reference reads
    prompt + served[:-1] and scores positions P-1 .. P+T-2.  With
    ``quant=True`` the tokens scored are those the float8 control puts
    first (the control reading).  Rows are padded to ``n_pad`` so one compiled
    reference serves every run."""
    import jax.numpy as jnp

    from bench import reference

    r, p = prompts.shape
    t = served.shape[1]
    seq = np.concatenate([prompts, served[:, :-1]], 1).astype(np.int32)
    pad = np.zeros((n_pad, seq.shape[1]), np.int32)
    pad[:r] = seq
    ref = np.asarray(reference.logits(dims, weights, jnp.asarray(pad)))[:r, p - 1:p - 1 + t]
    chosen = served
    if quant:
        ctl = np.asarray(reference.logits(dims, weights, jnp.asarray(pad), True))
        chosen = ctl[:r, p - 1:p - 1 + t].argmax(-1)
    return float(token_gaps(ref, chosen).max())
