"""A plain SneakPeek scheduler: the reference for the program's decisions.

Written from the paper (arXiv:2505.06641), one window at a time:

* posterior (Eq. 10-11): theta = (alpha + y) / sum(alpha + y), the k-NN
  votes ``y`` under the configuration's Jeffreys prior (alpha_i = 1/2);
* accuracy (Eq. 9): sum_i theta_i * recall_i(m);
* utility (Eq. 2): accuracy * (1 - gamma(d, e)), with the deadline ``d`` and
  the completion ``e`` on the serving clock, as the scheduler sees them;
* priority (Eq. 12, 14): (1 + Var[accuracies]) * exp(-(d - now)), the mean
  over a group;
* Algorithm 1 with the data-aware split of section V-C2: one group per
  application, split by the posterior's top label where it exceeds 1/2;
  with at most ``TAU`` groups every group order and variant choice is
  scored and the best plan kept, otherwise groups run by priority (one
  application's groups kept together) and each takes the variant of best
  mean member utility (Eq. 13) at the tail of the worker's queue;
* one worker whose queue (busy-until time and least-recently-used model
  residency under a byte capacity, Eq. 1) carries from window to window,
  advanced by the reference's own decisions.

Ties break as the program documents them: a plan replaces the best only
when strictly better, a variant by (utility, -latency, name), groups by
(-priority, key), members by (-priority, rid).  Sums run in the same order,
so equal inputs give equal bits.  The replay compares each window's
(request, variant, order, batch) tuples with what the program scheduled.
"""
from __future__ import annotations

import dataclasses
import itertools
import math

import numpy as np

JEFFREYS = 0.5  # Dirichlet prior concentration per class (paper section VI-C3)
TAU = 3  # at most this many groups are scheduled exactly (Algorithm 1)
SPLIT = 0.5  # a posterior above this on one label puts the request in that label's group
PRIORITY_FLOOR_S = -60.0  # time-to-deadline floor inside exp(-d)


@dataclasses.dataclass(frozen=True)
class Variant:
    """One model variant of an application, as the scheduler is told it."""

    name: str
    recalls: np.ndarray
    latency_s: float
    load_s: float
    size: int
    affine: tuple[float, float] | None = None  # (fixed_s, per_item_s)

    def latency(self, b: int) -> float:
        if self.affine is None:
            return self.latency_s * b
        return self.affine[0] + self.affine[1] * b


@dataclasses.dataclass
class Req:
    rid: int
    app: str
    arrival_s: float
    deadline_s: float
    votes: np.ndarray
    theta: np.ndarray = dataclasses.field(init=False)

    def __post_init__(self):
        a = JEFFREYS + np.asarray(self.votes, np.float64)
        self.theta = a / a.sum()


def gamma(kind: str, d: float, e: float) -> float:
    """Deadline penalty of the paper's section VI-A at deadline ``d`` and completion ``e``."""
    if e <= d:
        return 0.0
    if kind == "step":
        return 1.0
    if d <= 0:
        return 1.0
    x = (e - d) / d
    if kind == "linear":
        return min(1.0, x)
    if x >= 1.0:
        return 1.0
    if x <= 0.0:
        return 0.0
    ratio = x / (1.0 - x)
    return min(1.0, 1.0 / (1.0 + 1.0 / (ratio * ratio * ratio)))


class Queue:
    """One worker: busy-until time and LRU residency (oldest first)."""

    def __init__(self, t: float, capacity: int | None, resident=()):
        self.t = float(t)
        self.capacity = capacity
        self.resident = list(resident)

    def clone(self) -> "Queue":
        return Queue(self.t, self.capacity, self.resident)

    def swap(self, v: Variant) -> float:
        return 0.0 if v.name in self.resident else v.load_s

    def run(self, v: Variant, b: int, sizes: dict) -> tuple[float, float]:
        start = self.t
        if v.name in self.resident:
            self.resident.remove(v.name)
            self.resident.append(v.name)
            swap = 0.0
        else:
            swap = v.load_s
            self.resident.append(v.name)
            if self.capacity is None:
                self.resident = [v.name]
            else:
                total = sum(sizes[n] for n in self.resident)
                i = 0
                while total > self.capacity and i < len(self.resident):
                    if self.resident[i] == v.name:
                        i += 1
                        continue
                    total -= sizes[self.resident.pop(i)]
        self.t = start + swap + v.latency(b)
        return start, self.t


class Scheduler:
    """The reference, fed the applications' variants and the worker's memory."""

    def __init__(self, apps: dict, penalty: dict, capacity: int | None, sizes: dict):
        self.apps = apps  # app -> [Variant, ...] in the application's order
        self.penalty = penalty  # app -> penalty kind
        self.sizes = sizes
        self.queue = Queue(0.0, capacity)

    def accuracy(self, r: Req, v: Variant) -> float:
        return float(v.recalls @ r.theta)

    def utility(self, r: Req, v: Variant, start: float, lat: float) -> float:
        g = gamma(self.penalty[r.app], r.deadline_s, start + lat)
        return self.accuracy(r, v) * (1.0 - min(1.0, max(0.0, g)))

    def priority(self, r: Req, now: float) -> float:
        accs = np.array([self.accuracy(r, v) for v in self.apps[r.app]])
        var = float(accs.var()) if accs.size > 1 else 0.0
        return (1.0 + var) * math.exp(-max(r.deadline_s - now, PRIORITY_FLOOR_S))

    def groups(self, reqs: list[Req]) -> dict[str, list[Req]]:
        by_app: dict[str, list[Req]] = {}
        for r in reqs:
            by_app.setdefault(r.app, []).append(r)
        out = {}
        for app, members in by_app.items():
            buckets: dict[str, list[Req]] = {}
            for r in members:
                top = int(np.argmax(r.theta))
                key = f"label{top}" if r.theta[top] > SPLIT else "mixed"
                buckets.setdefault(key, []).append(r)
            if len(buckets) == 1:
                out[app] = members
            else:
                out.update({f"{app}#{k}": sub for k, sub in buckets.items()})
        return out

    def _plan_utility(self, plan, q: Queue) -> float:
        q = q.clone()
        total = 0.0
        for members, v in plan:
            start, done = q.run(v, len(members), self.sizes)
            lat = done - start
            for r in members:
                total += self.utility(r, v, start, lat)
        return total / max(1, sum(len(m) for m, _ in plan))

    def _exact(self, groups: dict, q: Queue) -> list[tuple[list[Req], Variant]]:
        best, best_u = None, -np.inf
        keys = sorted(groups)
        for perm in itertools.permutations(keys):
            members = [sorted(groups[k], key=lambda r: (r.deadline_s, r.rid)) for k in perm]
            for choice in itertools.product(*[self.apps[groups[k][0].app] for k in perm]):
                plan = list(zip(members, choice))
                u = self._plan_utility(plan, q)
                if u > best_u:
                    best, best_u = plan, u
        return best

    def _best_variant(self, members: list[Req], q: Queue) -> Variant:
        b = len(members)
        best, best_key = None, None
        for v in self.apps[members[0].app]:
            start = q.t
            lat = (q.t + q.swap(v) + v.latency(b)) - start
            total = 0.0
            for r in members:
                total += self.utility(r, v, start, lat)
            key = (total / b, -v.latency_s, v.name)
            if best is None or key > best_key:
                best, best_key = v, key
        return best

    def _greedy(self, groups: dict, q: Queue, now: float):
        prio = {k: float(np.mean([self.priority(r, now) for r in m])) for k, m in groups.items()}
        order = sorted(groups.items(), key=lambda kv: (-prio[kv[0]], kv[0]))
        if len(order) > 1:
            rank: dict[str, int] = {}
            for _, m in order:
                rank.setdefault(m[0].app, len(rank))
            order.sort(key=lambda kv: (rank[kv[1][0].app], -prio[kv[0]]))
        plan = []
        for _, members in order:
            v = self._best_variant(members, q)
            q.run(v, len(members), self.sizes)
            plan.append((sorted(members, key=lambda r: (-self.priority(r, now), r.rid)), v))
        return plan

    def window(self, reqs: list[Req], now: float) -> list[tuple[int, str, int, int]]:
        """Schedule one window at ``now`` and commit it to the worker's queue;
        returns (rid, variant, order, batch) in execution order."""
        if not reqs:
            return []
        reqs = sorted(reqs, key=lambda r: (r.arrival_s, r.rid))
        groups = self.groups(reqs)
        q = self.queue.clone()
        q.t = max(q.t, now)
        plan = self._exact(groups, q) if len(groups) <= TAU else self._greedy(groups, q, now)
        self.queue.t = max(self.queue.t, now)
        out, k = [], 1
        for batch, (members, v) in enumerate(plan):
            self.queue.run(v, len(members), self.sizes)
            for r in members:
                out.append((r.rid, v.name, k, batch))
                k += 1
        return out


def differing(program: list, reference: list) -> int:
    """Requests of one window whose (variant, order, batch) differ, or that
    only one side scheduled."""
    p = {d[0]: tuple(d[1:]) for d in program}
    r = {d[0]: tuple(d[1:]) for d in reference}
    return sum(p.get(k) != r.get(k) for k in p.keys() | r.keys())
