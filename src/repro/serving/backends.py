"""Pluggable executor backends: one execution interface, three substrates.

The scheduler (repro.core) is modeless — it consumes ``ModelProfile``
numbers and emits (model, order, batch, worker) placements without
caring what executes them.  This module makes the *execution* substrate
equally swappable: everything the runtime (``serving.runtime``) needs
from "a thing that runs models" is the ``ExecutorBackend`` interface —

    run_batch(model, prompts, request_ids) -> ExecutionReport
    latency_model(model, batch)            -> seconds
    model_bytes(model)                     -> bytes (weights + KV cache)
    swap_cost(model)                       -> cold-load seconds

Three implementations ship:

* ``ProfiledBackend`` — today's accounting path, extracted verbatim from
  the pre-refactor ``LMExecutor``: lazy param materialization, jitted
  prefill/decode on (reduced-config) JAX models, stopwatch timing.
  Default everywhere; bit-identical to the old hard-coded path.
* ``CompiledBackend`` — real jitted forward passes over
  ``configs/registry.py`` models with batch/sequence bucketing (bounds
  retraces), donated decode caches (``models/kvcache.py`` buffers are
  reused in place across decode steps), and per-window continuous
  batching via ``run_batches``.  Its latency model is FIT from realized
  (batch, seconds) observations — provenance ``"realized"``.
* ``CostModelBackend`` — no device execution: latencies come from the
  ``launch/costmodel.py``/dry-run roofline census through
  ``serving.profiles``; reports are synthetic (modelled seconds, no
  tokens).  Provenance ``"costmodel"``.

Each backend can mint scheduler-facing ``ModelProfile``s via
``profile()``; the profile's ``provenance`` field records which estimate
the drift correction (PR 6's realized/committed EWMA) is correcting.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.accuracy import ModelProfile
from repro.models import LM
from repro.models.kvcache import cache_bytes

__all__ = [
    "ExecutionReport",
    "ExecutorBackend",
    "ProfiledBackend",
    "CompiledBackend",
    "CostModelBackend",
    "SimulatedBackend",
]

_STAGING_BW = 25e9  # host->device weight staging bandwidth (B/s)


@dataclasses.dataclass
class ExecutionReport:
    """Realized execution of one scheduled batch (timing + outputs)."""

    request_ids: list
    model: str
    batch_size: int
    swap_s: float
    prefill_s: float
    decode_s: float
    tokens: np.ndarray  # (B, new_tokens) generated ids
    predictions: list  # per-request predicted class (argmax over option logits)
    worker: int = -1  # lane that executed the batch (-1: single-executor path)
    # Held-expert MoE counters of the forward that ran the batch (0 for other
    # models; of a fused forward, the first of its reports carries them):
    # (row, held expert) pairs routed over the prefill and every decode step,
    # padded rows included, and the held experts each decode step reached,
    # summed over MoE layers and steps.
    routed_pairs: int = 0
    experts_reached: int = 0

    @property
    def total_s(self) -> float:
        """Swap + prefill + decode seconds for the batch."""
        return self.swap_s + self.prefill_s + self.decode_s


def weight_bytes(cfg) -> int:
    """Parameter bytes for a config at its declared dtype."""
    per = 2 if cfg.dtype == "bfloat16" else 4
    return per * cfg.param_count()


def _affine_fit(obs: Sequence[tuple[int, float]]) -> tuple[float, float]:
    """(fixed_s, per_item_s) least-squares fit of (batch, seconds) points.

    The model stays physical: every forward pays a positive fixed cost
    (the weights are read once per pass) and no batch is cheaper than a
    smaller one.  One distinct batch size, or a fit whose intercept is
    not positive (noise steeper than the batch dependence it measures),
    yields a flat model at the mean of the per-batch means; a negative
    slope is clamped to zero.
    """
    if not obs:
        return 0.0, 0.0
    by_b: dict[int, list[float]] = {}
    for b, t in obs:
        by_b.setdefault(int(b), []).append(float(t))
    bs = sorted(by_b)
    ts = [sum(by_b[b]) / len(by_b[b]) for b in bs]
    if len(bs) < 2:
        return ts[0], 0.0
    slope, intercept = np.polyfit(np.asarray(bs, float), np.asarray(ts, float), 1)
    if intercept <= 0.0:
        return float(np.mean(ts)), 0.0
    return float(intercept), max(float(slope), 0.0)


class ExecutorBackend:
    """Interface every execution substrate implements.

    ``variants`` maps model name -> (ModelConfig, seed); ``provenance``
    labels the latency estimates this backend produces (``profiled`` /
    ``costmodel`` / ``realized``) and is stamped onto the
    ``ModelProfile``s it mints.
    """

    provenance: str = "profiled"
    # Forwards that ran a shape this backend had not run before (each a
    # compile on a jitting backend); only ``CompiledBackend`` counts them.
    cold_forwards: int = 0
    # Blocking device-to-host waits (two per forward); only the backends
    # that run real forwards count them.
    host_syncs: int = 0
    # Held-expert MoE counters summed over every forward (``ExecutionReport``
    # has one forward's); only the backends that run real forwards count.
    routed_pairs: int = 0
    experts_reached: int = 0

    def __init__(self, variants: Mapping[str, tuple], new_tokens: int = 4):
        self.variants = dict(variants)
        self.new_tokens = new_tokens
        self._obs: dict[str, list[tuple[int, float]]] = {}

    # -------------------------------------------------------- execution

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """Execute one padded (B, S) prompt batch; ``swap_s`` is left at
        0.0 — residency/swap accounting belongs to the caller's
        ``SwapManager``, not the substrate."""
        raise NotImplementedError

    # -------------------------------------------------------- estimates

    def _record(self, model_name: str, batch: int, seconds: float) -> None:
        self._obs.setdefault(model_name, []).append((int(batch), float(seconds)))

    def affine(self, model_name: str) -> tuple[float, float]:
        """(fixed_s, per_item_s) latency model for one variant."""
        return _affine_fit(self._obs.get(model_name, []))

    def latency_model(self, model_name: str, batch: int = 1) -> float:
        """Estimated seconds to execute a batch of ``batch`` requests."""
        fixed, per_item = self.affine(model_name)
        return fixed + per_item * batch

    def model_bytes(self, model_name: str, batch: int | None = None,
                    max_len: int | None = None) -> int:
        """Device bytes a resident variant occupies (weights only here;
        subclasses that model the KV cache add it)."""
        cfg, _ = self.variants[model_name]
        return weight_bytes(cfg)

    def swap_cost(self, model_name: str) -> float:
        """Seconds to stage a cold variant's weights onto the device."""
        return self.model_bytes(model_name) / _STAGING_BW

    # ------------------------------------------------------- lifecycle

    def spawn(self) -> "ExecutorBackend":
        """A fresh same-config instance for a new lane (per-worker
        residency and jit caches, exactly like a real per-worker
        device)."""
        return type(self)(self.variants, new_tokens=self.new_tokens)

    def close(self) -> None:
        """Release resources the substrate holds (default: nothing —
        only substrates owning external resources, e.g. a process lane's
        spawned worker, override this)."""

    def profile(self, model_name: str, recalls, name: str | None = None,
                latency_floor_s: float = 0.0) -> ModelProfile:
        """Mint a scheduler-facing ``ModelProfile`` from this backend's
        own latency/memory/swap estimates, stamped with its provenance."""
        fixed, per_item = self.affine(model_name)
        lat = max(fixed + per_item, latency_floor_s)
        return ModelProfile(
            name=name or model_name,
            recalls=np.asarray(recalls, dtype=np.float64),
            latency_s=lat,
            load_latency_s=self.swap_cost(model_name),
            memory_bytes=self.model_bytes(model_name),
            latency_model=(max(fixed, lat - per_item), per_item),
            provenance=self.provenance,
        )


def _greedy(logits):
    """The greedy pick: the first index of each row's largest logit."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _programs(model: LM, new_tokens: int, donate_cache: bool):
    """The jitted prefill and decode step of one model, each ending in the
    greedy pick, so a forward's tokens stay on the device until it reads
    them back once.  Named functions, so their modules read
    ``jit_prefill`` and ``jit_decode_step`` in a profiler trace.

    ``prefill(params, tokens, class_ids=None) -> (tok, cache, preds, n)``:
    ``tok`` is the (B,) int32 pick from the last position's logits and
    ``preds`` the pick among the logits of ``class_ids`` (None without
    them).  ``decode_step(params, cache, tok) -> (tok, cache, n)`` feeds the
    (B,) tokens back as one position each.  ``n`` is the held-expert MoE
    counters of the program (int32 (2,): routed pairs, experts reached), or
    None for a model without such layers.
    """
    kw = {"counts": True} if model.cfg.counts_experts else {}

    def prefill(params, tokens, class_ids=None):
        logits, cache, *n = model.prefill(params, tokens, max_len=tokens.shape[1] + new_tokens,
                                          **kw)
        preds = None if class_ids is None else jnp.argmax(logits[:, class_ids], axis=-1)
        return _greedy(logits), cache, preds, (n[0] if n else None)

    def decode_step(params, cache, tok):
        logits, cache, *n = model.decode_step(params, cache, tok[:, None], **kw)
        return _greedy(logits), cache, (n[0] if n else None)

    return jax.jit(prefill), jax.jit(decode_step, donate_argnums=(1,) if donate_cache else ())


class _ProgramBackend(ExecutorBackend):
    """Base of the backends that run real forwards: one ``LM``, its
    weights and its two programs (``_programs``) per variant, built on
    first use, and the greedy forward over them."""

    # Whether the decode step donates its cache (updated in place).
    donate_cache = False

    def __init__(self, variants: Mapping[str, tuple], new_tokens: int = 4):
        super().__init__(variants, new_tokens)
        self._models: dict[str, LM] = {}
        self._params: dict[str, dict] = {}
        self._prefill_jit: dict[str, Callable] = {}
        self._decode_jit: dict[str, Callable] = {}
        self.host_syncs = 0
        self.routed_pairs = 0
        self.experts_reached = 0

    def _get(self, name: str):
        if name not in self._models:
            cfg, seed = self.variants[name]
            model = LM(cfg)
            self._models[name] = model
            self._params[name] = model.init(seed)
            self._prefill_jit[name], self._decode_jit[name] = _programs(
                model, self.new_tokens, donate_cache=self.donate_cache)
        return self._models[name], self._params[name]

    def _generate(self, model_name: str, prompts: np.ndarray,
                  class_token_ids: Optional[np.ndarray]):
        """Greedy decoding of ``new_tokens`` tokens for (B, S) ``prompts``;
        returns (prefill_s, decode_s, tokens (B, new_tokens), preds or
        None).  The host waits twice: for the prefill's token, which ends
        ``prefill_s``, and for one readback of every token (and ``preds``,
        and the held-expert counters, which it adds to the backend's) once
        the last is ready, which ends ``decode_s``."""
        _, params = self._get(model_name)
        class_ids = (None if class_token_ids is None
                     else jnp.asarray(class_token_ids, jnp.int32))
        t0 = time.perf_counter()
        with tracing.span("exec.prefill"):
            tok, cache, preds, n = self._prefill_jit[model_name](
                params, jnp.asarray(prompts), class_ids)
            tok.block_until_ready()
            self.host_syncs += 1
        t1 = time.perf_counter()
        with tracing.span("exec.decode"):
            toks, counts = [tok], [n]
            for _ in range(self.new_tokens - 1):
                tok, cache, n = self._decode_jit[model_name](params, cache, tok)
                toks.append(tok)
                counts.append(n)
            toks, preds, counts = jax.device_get((toks, preds, counts))
            self.host_syncs += 1
        t2 = time.perf_counter()
        if counts[0] is not None:
            self.routed_pairs += int(sum(int(c[0]) for c in counts))
            self.experts_reached += int(sum(int(c[1]) for c in counts[1:]))
        return t1 - t0, t2 - t1, np.stack(toks, axis=1), preds

    @contextlib.contextmanager
    def _forward_span(self, model_name: str, rows: int, padded: int, rids):
        """The ``exec.forward`` span, given the host waits of its forward
        as ``syncs`` once they are known, and for a held-expert MoE model
        its ``routed_pairs`` and ``experts_reached``.  Yields a dict that
        holds those two counters of the forward at the end."""
        sp = tracing.span("exec.forward", model=model_name, rows=rows, padded=padded,
                          rids=rids)
        syncs, pairs, reached = self.host_syncs, self.routed_pairs, self.experts_reached
        counts = {}
        with sp:
            yield counts
            counts.update(routed_pairs=self.routed_pairs - pairs,
                          experts_reached=self.experts_reached - reached)
            meta = {"syncs": self.host_syncs - syncs}
            if self.variants[model_name][0].counts_experts:
                meta.update(counts)
            sp.set_metadata(**meta)


class ProfiledBackend(_ProgramBackend):
    """Today's accounting path, extracted from the pre-refactor
    ``LMExecutor`` with bit-identical defaults: lazy ``LM`` construction
    per variant, jitted prefill (static ``max_len = prompt + new_tokens``)
    and decode step, stopwatch-timed.  Sizes are weight bytes at the
    declared dtype; swap cost is bytes over the 25 GB/s staging rate —
    the exact constants the old executor asserted.
    """

    provenance = "profiled"

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """prompts: (B, S) int32 (pre-padded)."""
        b = prompts.shape[0]
        with self._forward_span(model_name, b, b, request_ids) as counts:
            prefill_s, decode_s, tokens, preds = self._generate(
                model_name, prompts, class_token_ids)
            self._record(model_name, b, prefill_s + decode_s)
        return ExecutionReport(
            request_ids=request_ids,
            model=model_name,
            batch_size=b,
            swap_s=0.0,
            prefill_s=prefill_s,
            decode_s=decode_s,
            tokens=tokens,
            predictions=list(preds) if preds is not None else [None] * b,
            **counts,
        )


def _bucket_batch(b: int) -> int:
    """Next power of two: bounds the distinct batch shapes jit sees."""
    return 1 << max(b - 1, 0).bit_length()


def _bucket_seq(s: int, multiple: int) -> int:
    """Round a sequence length up to the padding multiple."""
    return max(((s + multiple - 1) // multiple) * multiple, multiple)


class CompiledBackend(_ProgramBackend):
    """Real jitted forwards over registry models, serving-shaped.

    Differences from ``ProfiledBackend`` (which times whatever shape the
    schedule hands it):

    * **Bucketing** — batch pads to the next power of two and sequence
      length to a multiple of ``seq_multiple``, so the jit cache holds a
      bounded set of compiled shapes instead of one per ragged batch.
    * **Decode-cache reuse** — the decode step is jitted with the cache
      argument donated (``donate_argnums``), so XLA updates the
      ``models/kvcache.py`` buffers in place across the decode loop
      instead of allocating a fresh cache per token.
    * **Continuous batching** — ``run_batches`` fuses a window's run of
      same-model batches into ONE forward pass and splits the measured
      seconds back per scheduled batch (proportional to rows), which is
      what a serving window actually dispatches.
    * **Realized latency model** — every executed (padded batch,
      seconds) pair feeds an affine fit; ``latency_model``/``profile``
      self-calibrate with two dummy batches when asked before any real
      work ran.  Provenance ``"realized"``.

    ``model_bytes`` accounts weights PLUS the KV cache at the batch/
    length hints — the real residency cost of serving the variant, which
    the ``SwapManager`` and the scheduler's capacity-aware LRU consume.
    """

    provenance = "realized"
    # Donating the cache lets XLA reuse its buffers in place across decode
    # steps (the cache pytree dominates activation memory at serving batch
    # sizes).
    donate_cache = True

    def __init__(self, variants: Mapping[str, tuple], new_tokens: int = 4,
                 seq_multiple: int = 8, batch_hint: int = 8,
                 max_len_hint: int | None = None):
        super().__init__(variants, new_tokens)
        self.seq_multiple = int(seq_multiple)
        self.batch_hint = int(batch_hint)
        self.max_len_hint = max_len_hint
        # Shapes already executed once (compiled): only their runs feed
        # the latency fit, so one-off jit compile time never pollutes the
        # steady-state affine model; the others count as cold forwards.
        self._warm: set[tuple[str, int, int]] = set()
        self.cold_forwards = 0

    def spawn(self) -> "CompiledBackend":
        """Fresh lane instance sharing the shape-bucketing hints."""
        return CompiledBackend(
            self.variants, new_tokens=self.new_tokens,
            seq_multiple=self.seq_multiple, batch_hint=self.batch_hint,
            max_len_hint=self.max_len_hint,
        )

    def _pad(self, prompts: np.ndarray) -> np.ndarray:
        b, s = prompts.shape
        bp = _bucket_batch(b)
        sp = _bucket_seq(s, self.seq_multiple)
        if (bp, sp) == (b, s):
            return prompts
        out = np.zeros((bp, sp), np.int32)
        out[:b, :s] = prompts
        return out

    def _forward(self, model_name: str, padded: np.ndarray,
                 class_token_ids: Optional[np.ndarray]):
        """One bucketed forward; returns (prefill_s, decode_s, tokens,
        preds) for ALL padded rows and records the latency observation."""
        prefill_s, decode_s, tokens, preds = self._generate(
            model_name, padded, class_token_ids)
        key = (model_name, padded.shape[0], padded.shape[1])
        if key in self._warm:
            self._record(model_name, padded.shape[0], prefill_s + decode_s)
        else:
            self._warm.add(key)
            self.cold_forwards += 1
        return prefill_s, decode_s, tokens, preds

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """One bucketed jitted forward for a scheduled batch; the report
        carries the UNPADDED rows (timing covers the padded shape)."""
        b = prompts.shape[0]
        padded = self._pad(prompts)
        with self._forward_span(model_name, b, padded.shape[0], request_ids) as counts:
            prefill_s, decode_s, tokens, preds = self._forward(
                model_name, padded, class_token_ids)
        return ExecutionReport(
            request_ids=request_ids, model=model_name, batch_size=b,
            swap_s=0.0, prefill_s=prefill_s, decode_s=decode_s,
            tokens=tokens[:b],
            predictions=list(preds[:b]) if preds is not None else [None] * b,
            **counts,
        )

    def run_batches(self, model_name: str, prompt_list: Sequence[np.ndarray],
                    rid_lists: Sequence[list],
                    class_token_ids: Optional[np.ndarray] = None) -> list[ExecutionReport]:
        """Continuous batching: fuse several scheduled batches of the
        same model into one forward, then split outputs and measured
        seconds back per batch (time proportional to rows — the fused
        pass has no per-batch boundary)."""
        sizes = [p.shape[0] for p in prompt_list]
        maxlen = max(p.shape[1] for p in prompt_list)
        total = sum(sizes)
        merged = np.zeros((total, maxlen), np.int32)
        row = 0
        for p in prompt_list:
            merged[row:row + p.shape[0], :p.shape[1]] = p
            row += p.shape[0]
        padded = self._pad(merged)
        with self._forward_span(model_name, total, padded.shape[0], rid_lists) as counts:
            prefill_s, decode_s, tokens, preds = self._forward(
                model_name, padded, class_token_ids)
        reports = []
        row = 0
        for b, rids in zip(sizes, rid_lists):
            frac = b / total
            reports.append(ExecutionReport(
                request_ids=list(rids), model=model_name, batch_size=b,
                swap_s=0.0, prefill_s=prefill_s * frac, decode_s=decode_s * frac,
                tokens=tokens[row:row + b],
                predictions=(list(preds[row:row + b]) if preds is not None
                             else [None] * b),
                **(counts if row == 0 else {}),
            ))
            row += b
        return reports

    # -------------------------------------------------------- estimates

    def _calibrate(self, model_name: str) -> None:
        """Seed the affine fit with dummy forwards at two bucketed batch
        sizes when latency is queried before any real work ran.  Each
        shape runs twice: the first run compiles (unrecorded), the second
        is the warm observation the fit consumes."""
        for b in (1, 2):
            dummy = np.zeros((b, self.seq_multiple), np.int32)
            for _ in range(2):
                self.run_batch(model_name, dummy, list(range(b)))

    def affine(self, model_name: str) -> tuple[float, float]:
        """Realized-latency fit; self-calibrates if too few shapes ran."""
        obs = self._obs.get(model_name, [])
        if len({b for b, _ in obs}) < 2:
            self._calibrate(model_name)
        return _affine_fit(self._obs[model_name])

    def model_bytes(self, model_name: str, batch: int | None = None,
                    max_len: int | None = None) -> int:
        """Weights plus the KV cache at the batch/length hints — the real
        residency cost of serving the variant."""
        cfg, _ = self.variants[model_name]
        b = batch if batch is not None else self.batch_hint
        if max_len is None:
            max_len = self.max_len_hint
        if max_len is None:
            max_len = _bucket_seq(64, self.seq_multiple) + self.new_tokens
        return weight_bytes(cfg) + cache_bytes(cfg, b, max_len)


class SimulatedBackend(ExecutorBackend):
    """Deterministic no-model substrate built straight from scheduler
    ``ModelProfile``s — no ``ModelConfig``, no device, no jit.

    Reported seconds are ALWAYS the profile's modelled latency
    (``latency_model`` affine, or flat ``latency_s``), so every run —
    any lane strategy, sync or overlapped — sees bit-identical reports
    and therefore makes bit-identical scheduling decisions.  What varies
    is only how long the call really occupies its lane:

    * ``occupancy="none"`` — return immediately (pure accounting).
    * ``occupancy="sleep"`` — hold the lane for the modelled seconds
      (× ``time_scale``) in ``time.sleep``, which releases the GIL: the
      shape of a device-bound forward.  The lane benchmark's substrate.
    * ``occupancy="spin"`` — busy-wait the same duration WITHOUT
      releasing the GIL: the shape of host-bound Python work, the case
      the process lane exists for.

    Instances hold no unpicklable state, so they cross the process-lane
    pipe as-is; predictions are a deterministic per-(rid, model) hash so
    outputs match across lanes and processes.
    """

    provenance = "simulated"

    OCCUPANCY = ("none", "sleep", "spin")

    def __init__(self, profiles: Mapping[str, ModelProfile], new_tokens: int = 0,
                 occupancy: str = "none", time_scale: float = 1.0):
        if occupancy not in self.OCCUPANCY:
            raise ValueError(f"unknown occupancy {occupancy!r}; "
                             f"expected one of {self.OCCUPANCY}")
        super().__init__({name: (prof, 0) for name, prof in dict(profiles).items()},
                         new_tokens)
        self.profiles = dict(profiles)
        self.occupancy = occupancy
        self.time_scale = float(time_scale)

    def spawn(self) -> "SimulatedBackend":
        """Fresh lane instance sharing profiles and occupancy mode."""
        return SimulatedBackend(self.profiles, new_tokens=self.new_tokens,
                                occupancy=self.occupancy, time_scale=self.time_scale)

    def affine(self, model_name: str) -> tuple[float, float]:
        """The profile's declared latency model (flat if it has none)."""
        prof = self.profiles[model_name]
        if prof.latency_model is not None:
            return float(prof.latency_model[0]), float(prof.latency_model[1])
        return float(prof.latency_s), 0.0

    def model_bytes(self, model_name: str, batch: int | None = None,
                    max_len: int | None = None) -> int:
        """The profile's declared residency footprint."""
        return int(self.profiles[model_name].memory_bytes)

    def swap_cost(self, model_name: str) -> float:
        """The profile's declared cold-load seconds."""
        return float(self.profiles[model_name].load_latency_s)

    def _occupy(self, seconds: float) -> None:
        if seconds <= 0.0 or self.occupancy == "none":
            return
        if self.occupancy == "sleep":
            time.sleep(seconds)
            return
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            pass

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """Occupy the lane per the occupancy mode, report the modelled
        seconds, and emit deterministic per-request predictions."""
        b = prompts.shape[0]
        fixed, per_item = self.affine(model_name)
        total = fixed + per_item * b
        self._occupy(total * self.time_scale)
        self._record(model_name, b, total)
        n_classes = max(len(self.profiles[model_name].recalls), 1)
        preds = [int((int(rid) * 1103515245 + len(model_name)) % n_classes)
                 for rid in request_ids]
        return ExecutionReport(
            request_ids=list(request_ids), model=model_name, batch_size=b,
            swap_s=0.0, prefill_s=total, decode_s=0.0,
            tokens=np.zeros((b, 0), np.int32),
            predictions=preds,
        )


class CostModelBackend(ExecutorBackend):
    """Latency from the roofline cost model — no device execution.

    Every estimate flows through ``serving.profiles``: dry-run roofline
    artifacts when ``results_dir`` has them, ``launch/costmodel.py``
    ``composed_cost`` totals when passed via ``costs=``, and the analytic
    roofline census (``launch/hlo_analysis.HW`` constants +
    ``models/kvcache.cache_bytes`` for decode cache reads) otherwise.
    ``run_batch`` returns a synthetic ``ExecutionReport`` whose timing
    fields carry the MODELLED seconds (split prefill/decode by the
    census's proportions) with no generated tokens — this backend exists
    to drive schedulers and capacity planning for variants too large to
    execute here.  Provenance ``"costmodel"``.

    ``variants`` accepts the executor convention ``{name: (cfg, seed)}``
    or bare configs / registry arch names.
    """

    provenance = "costmodel"

    def __init__(self, variants: Mapping, prompt_tokens: int = 512,
                 new_tokens: int = 64, results_dir=None, mesh: str = "pod",
                 n_devices: int = 16, costs: Mapping[str, Mapping] | None = None,
                 batch_hint: int = 8):
        from repro.configs import get_config

        norm = {}
        for name, v in dict(variants).items():
            if isinstance(v, tuple):
                norm[name] = v
            elif isinstance(v, str):
                norm[name] = (get_config(v), 0)
            else:
                norm[name] = (v, 0)
        super().__init__(norm, new_tokens)
        self.prompt_tokens = int(prompt_tokens)
        self.results_dir = results_dir
        self.mesh = mesh
        self.n_devices = int(n_devices)
        self.costs = dict(costs) if costs else {}
        self.batch_hint = int(batch_hint)
        self._affine_cache: dict[str, tuple[float, float]] = {}

    def spawn(self) -> "CostModelBackend":
        """Fresh lane instance sharing the cost-model parameters."""
        return CostModelBackend(
            self.variants, prompt_tokens=self.prompt_tokens,
            new_tokens=self.new_tokens, results_dir=self.results_dir,
            mesh=self.mesh, n_devices=self.n_devices, costs=self.costs,
            batch_hint=self.batch_hint,
        )

    def affine(self, model_name: str) -> tuple[float, float]:
        """(fixed_s, per_item_s) from the roofline cost model (cached)."""
        if model_name not in self._affine_cache:
            from repro.serving.profiles import costmodel_latency_model

            cfg, _ = self.variants[model_name]
            self._affine_cache[model_name] = costmodel_latency_model(
                cfg, prompt_tokens=self.prompt_tokens,
                new_tokens=self.new_tokens, results_dir=self.results_dir,
                mesh=self.mesh, n_devices=self.n_devices,
                costs=self.costs.get(model_name),
            )
        return self._affine_cache[model_name]

    def run_batch(self, model_name: str, prompts: np.ndarray, request_ids: list,
                  class_token_ids: Optional[np.ndarray] = None) -> ExecutionReport:
        """Synthetic report: modelled seconds (census prefill/decode
        split), zero generated tokens, no predictions."""
        from repro.serving.profiles import costmodel_terms

        b = prompts.shape[0]
        fixed, per_item = self.affine(model_name)
        total = fixed + per_item * b
        cfg, _ = self.variants[model_name]
        terms = costmodel_terms(cfg, prompt_tokens=self.prompt_tokens,
                                new_tokens=self.new_tokens,
                                n_devices=self.n_devices)
        census_prefill = terms["prefill_fixed_s"] + terms["prefill_item_s"] * b
        census_total = census_prefill + terms["decode_fixed_s"] + terms["decode_item_s"] * b
        pf = census_prefill / census_total if census_total > 0 else 0.0
        return ExecutionReport(
            request_ids=request_ids, model=model_name, batch_size=b,
            swap_s=0.0, prefill_s=total * pf, decode_s=total * (1.0 - pf),
            tokens=np.zeros((b, 0), np.int32),
            predictions=[None] * b,
        )

    def model_bytes(self, model_name: str, batch: int | None = None,
                    max_len: int | None = None) -> int:
        """Weights plus the KV cache at the modelled serving shape."""
        cfg, _ = self.variants[model_name]
        b = batch if batch is not None else self.batch_hint
        if max_len is None:
            max_len = self.prompt_tokens + self.new_tokens
        return weight_bytes(cfg) + cache_bytes(cfg, b, max_len)

    def swap_cost(self, model_name: str) -> float:
        """Pod serving: per-device weight shards stage in parallel over
        the DCN — the same rate ``lm_profile`` charges."""
        cfg, _ = self.variants[model_name]
        return weight_bytes(cfg) / _STAGING_BW / self.n_devices

    def profiles(self, recalls: Mapping[str, Sequence[float]]) -> dict[str, ModelProfile]:
        """Mint one costmodel-provenance ``ModelProfile`` per variant."""
        return {name: self.profile(name, rec) for name, rec in recalls.items()}
