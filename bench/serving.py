"""Thin subclasses that record what the timed path does, and nothing more.

``StampedBackend`` is the program's ``CompiledBackend``: it only stamps the
wall time at which each forward returned (its outputs are host arrays by
then, so the device has finished), keeps the served tokens, and records
each forward's shape and its ``prefill_s`` / ``decode_s``.  Its weights are
the benchmark's (``bench.weights``), handed to the program's own ``_get``.

``TimedKNN`` is the program's ``KNNSneakPeek``: it times each
``evidence_batch`` on the host clock and keeps its queries and votes for the
comparison with the reference.
"""
from __future__ import annotations

import contextlib
import time

import jax

from repro.core.sneakpeek import KNNSneakPeek
from repro.models import LM
from repro.serving.backends import CompiledBackend

_NULL = contextlib.nullcontext()


def span(name: str, on: bool):
    """A profiler host span when tracing, else nothing."""
    return jax.profiler.TraceAnnotation(name) if on else _NULL


@contextlib.contextmanager
def _weights_from(table: dict):
    """While active, ``LM.init`` returns the benchmark's weights for the
    model's name instead of drawing its own."""
    orig = LM.init
    LM.init = lambda self, seed=0: table[self.cfg.name]
    try:
        yield
    finally:
        LM.init = orig


class StampedBackend(CompiledBackend):
    """``CompiledBackend`` that stamps completions and records forwards."""

    def __init__(self, variants, weights: dict, **kw):
        super().__init__(variants, **kw)
        self.weights = weights
        self.trace = False
        self.done: dict[int, float] = {}  # rid -> perf_counter when its forward returned
        self.tokens: dict[int, object] = {}  # rid -> served token ids
        self.served_by: dict[int, str] = {}
        self.forwards: list[dict] = []

    def spawn(self):
        raise NotImplementedError("the benchmark serves one lane")

    def _get(self, name: str):
        if name not in self._models:
            with _weights_from(self.weights):
                return super()._get(name)
        return super()._get(name)

    def _note(self, model: str, reports, rows: int, seq: int) -> None:
        now = time.perf_counter()
        for rep in reports:
            for k, rid in enumerate(rep.request_ids):
                self.done[rid] = now
                self.tokens[rid] = rep.tokens[k]
                self.served_by[rid] = model
        self.forwards.append({
            "t": now, "model": model, "rows": rows, "padded": 1 << max(rows - 1, 0).bit_length(),
            "seq": seq, "prefill_s": sum(r.prefill_s for r in reports),
            "decode_s": sum(r.decode_s for r in reports),
        })

    def run_batch(self, model_name, prompts, request_ids, class_token_ids=None):
        with span("bench.exec", self.trace):
            rep = super().run_batch(model_name, prompts, request_ids, class_token_ids)
        self._note(model_name, [rep], prompts.shape[0], prompts.shape[1])
        return rep

    def run_batches(self, model_name, prompt_list, rid_lists, class_token_ids=None):
        with span("bench.exec", self.trace):
            reps = super().run_batches(model_name, prompt_list, rid_lists, class_token_ids)
        self._note(model_name, reps, sum(p.shape[0] for p in prompt_list),
                   max(p.shape[1] for p in prompt_list))
        return reps

    def free(self) -> None:
        """Drop the compiled programs and the program's handles on the weights."""
        self._prefill_jit.clear()
        self._decode_jit.clear()
        self._params.clear()
        self._models.clear()


class TimedKNN(KNNSneakPeek):
    """``KNNSneakPeek`` that times and keeps each ``evidence_batch``."""

    trace = False

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.seconds = 0.0
        self.calls: list[tuple] = []  # (queries, votes)

    def evidence_batch(self, features, true_labels=None):
        with span("bench.ingest", self.trace):
            t0 = time.perf_counter()
            votes = super().evidence_batch(features, true_labels)
            self.seconds += time.perf_counter() - t0
        self.calls.append((features, votes))
        return votes
