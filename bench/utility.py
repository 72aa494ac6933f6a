"""The benchmark's copy of the paper's request utility (Eq. 2) and penalties.

    u = recall(served model, true label) * (1 - gamma(d, e))

The benchmark evaluates gamma on times measured from the request's due
time: ``d`` is the deadline budget and ``e`` the realized completion
latency, so the overshoot ratio (e - d) / d does not depend on when the
stream started.  Kept here so that the yardstick cannot move with the
program's ``core/utility.py``.
"""
from __future__ import annotations

import numpy as np


def step_penalty(d, e):
    """1 on any miss."""
    return np.where(np.asarray(e) > np.asarray(d), 1.0, 0.0)


def linear_penalty(d, e):
    """Overshoot fraction of the deadline, capped at 1."""
    d, e = np.asarray(d, np.float64), np.asarray(e, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        ramp = np.minimum(1.0, (e - d) / d)
    return np.where(e <= d, 0.0, np.where(d <= 0, 1.0, ramp))


def sigmoid_penalty(d, e):
    """Rational sigmoid of the overshoot ratio x = (e - d) / d, 1 from x >= 1."""
    d, e = np.asarray(d, np.float64), np.asarray(e, np.float64)
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        x = (e - d) / d
        ratio = x / (1.0 - x)
        inner = np.minimum(1.0, 1.0 / (1.0 + 1.0 / (ratio * ratio * ratio)))
    return np.where(e <= d, 0.0, np.where((d <= 0) | (x >= 1.0), 1.0,
                                          np.where(x <= 0.0, 0.0, inner)))


PENALTIES = {"step": step_penalty, "linear": linear_penalty, "sigmoid": sigmoid_penalty}


def realized_utility(recall, budget_s, latency_s, penalty: str = "sigmoid"):
    """Eq. 2 per request; a request never served (latency inf) scores 0."""
    recall = np.asarray(recall, np.float64)
    lat = np.asarray(latency_s, np.float64)
    served = np.isfinite(lat)
    g = PENALTIES[penalty](budget_s, np.where(served, lat, 0.0))
    return np.where(served, recall * (1.0 - np.clip(g, 0.0, 1.0)), 0.0)
