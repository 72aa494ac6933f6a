"""Device-resident window pipeline: ingest -> posterior -> Eq. 9/12 -> Eq. 2/13.

The fast path (repro.core.fastpath) vectorized the paper's equations but
still splits one scheduling window across the host/device boundary: the
SneakPeek stage runs per request in Python, the Eq. 9/12 matrices run as
numpy (or one stacked device program), and the Eq. 2/13 *selection* —
the argmax that actually picks a model — stays a host loop.  This module
fuses the whole window data plane into compiled programs:

  * **Ingest** — ``sneakpeek.ingest_window``: one batched evidence
    compute per application (k-NN votes through the Pallas kernel when
    the SneakPeek model uses the jax backend) followed by one batched
    Dirichlet update (``dirichlet.posterior_mean_batch``, Eq. 11).
  * **Per-request policies** (MaxAcc / LO-EDF / LO-Priority) — ONE
    jitted program per window: Eq. 9 sharpened accuracies, Eq. 12
    priorities, the window ordering (``lexsort``), and the Eq. 2/13
    selection.  MaxAcc selects with a whole-window argmax tile; the
    locally-optimal policies run a ``lax.scan`` that threads the
    queue-tail time and single-slot model residency through the
    sequential selection (the loop the ROADMAP called out as
    host-bound), scoring all candidate models of each step at once.
  * **Grouped policies** (Grouped / SneakPeek) — the stacked Eq. 9/12
    program (``fastpath.precompute_windows`` with the jax backend) plus
    a jitted ``lax.scan`` over the ordered groups, each step one greedy
    (members x models) Eq. 13 utility tile reduced to a masked mean and
    an argmax.  The brute-force branch (<= tau groups) delegates to the
    exact host solver, exactly as the fast path does.
  * **Multi-worker placement** (paper §VII, Eq. 15) — a jitted
    ``lax.scan`` over the priority-ordered groups whose body scores the
    FULL (worker, model) utility tile, picks the argmax under the shared
    tie-break (utility, -scaled latency, name, -wid) via a precomputed
    preference permutation, and threads the per-worker busy-until times
    and LRU residency slots functionally.  Worker state is the same
    array encoding the numpy fast path uses (``fastpath.PoolArrays``).

Residency is array-encoded everywhere: every scan carries fixed-size LRU
slot vectors updated by the compiled form of
``residency.touch_lru_array`` — capacity-aware multi-model eviction
included, with the paper's conservative single-slot model folded in via
``residency.single_slot_encoding`` (no host fallback for carried
capacity states).

Programs run under ``jax.enable_x64(True)`` so decisions match
the float64 numpy fast path and the scalar reference (the parity suite
in tests/test_pipeline.py asserts identical schedules for all five
policies, single- and multi-worker, with and without capacity limits).
Compiled programs are cached by their static configuration (policy knobs
+ per-app shape signature), so streaming runs with steady window shapes
reuse them across windows.

Escape hatches mirror the fast path's: ``make_policy(name,
pipeline=True)`` turns the pipeline on per policy (default off),
``set_pipeline_backend("numpy")`` routes every pipeline schedule through
the numpy fast path (decision-identical, no JAX needed), and the scalar
reference remains ``make_policy(name, fastpath=False)``.
"""
from __future__ import annotations

import time
from typing import Mapping, Sequence

import numpy as np

from repro.core.fastpath import (
    WindowArrays,
    fast_grouped_schedule,
    fast_per_request_schedule,
    ordered_group_items,
    precompute_windows,
)
from repro.core.sneakpeek import ingest_window
from repro.core.types import Application, Request, Schedule, ScheduleEntry

__all__ = [
    "WindowPipeline",
    "pipeline_schedule",
    "set_pipeline_backend",
    "get_pipeline_backend",
]

_PIPELINE_BACKEND = "auto"
_PENALTY_ID = {"step": 0, "linear": 1, "sigmoid": 2, "none": 3}
# Scan unroll factors, audited against the chunked programs (the bench
# artifact records the measured rationale — benchmarks/sched_bench.py
# emits an "unroll" block).  The sequential selection scans carry one
# utility tile per step, so unrolling mostly amortizes loop overhead:
# the per-request body is smallest (one (M,) tile) and takes the largest
# factor; the grouped/multi-worker bodies carry (B, M)/(W, B, M) tiles,
# so a lower factor keeps compile time flat for the same throughput.
# The chunked carry-reconstruction chains are scalar-cheap and sit
# inside a while_loop whose cost is dominated by the two batched tiles
# per round — a moderate unroll is enough there.
_UNROLL = {
    "per_request": 8,
    "grouped": 4,
    "multiworker": 4,
    "chunk_chain": 4,
}
# Compiled window programs keyed by static configuration; jit's own cache
# then keys on array shapes, so steady streaming windows recompile once.
_PROGRAMS: dict = {}
# Per-app-set static tables (swap/latency/residency-id/penalty, tie-pref
# order), window-independent: built once and reused across windows.  The
# cache holds the AppArrays refs it was built from, so the id key stays
# sound (AppArrays itself is memoized per Application); bounded LRU so
# retired application sets don't pin their arrays forever.
_TABLES: dict = {}
_TABLES_MAX = 16


def set_pipeline_backend(name: str) -> None:
    """Select the pipeline backend: "auto" (jax when available), "jax",
    or "numpy" (delegate to the decision-identical numpy fast path)."""
    global _PIPELINE_BACKEND
    if name not in ("auto", "jax", "numpy"):
        raise ValueError(f"unknown pipeline backend {name!r}")
    _PIPELINE_BACKEND = name


def get_pipeline_backend() -> str:
    """Current pipeline backend setting ("numpy", "jax" or "auto")."""
    return _PIPELINE_BACKEND


def _have_jax() -> bool:
    try:
        import jax  # noqa: F401
    except ImportError:
        return False
    return True


# --------------------------------------------------------------------------
# Jitted program builders
# --------------------------------------------------------------------------


def _penalty_jnp(pen_id, d, e):
    """Eq. 2 penalty gamma(d, e) selected by per-app id, branchless.

    Mirrors repro.core.utility's ndarray forms (step / linear / sigmoid /
    none) with nested selects; out-of-branch NaN/inf lanes are discarded
    by the outer ``where``s exactly like the numpy errstate guards.
    """
    import jax.numpy as jnp

    step = jnp.where(d < e, 1.0, 0.0)
    x = (e - d) / d
    linear = jnp.where(e <= d, 0.0, jnp.where(d <= 0, 1.0, jnp.minimum(1.0, x)))
    ratio = x / (1.0 - x)
    # Multiply/divide-only ratio^-3 (no pow): XLA's pow is not correctly
    # rounded, *, / are — keeps the device penalty bit-identical to the
    # numpy/scalar forms in repro.core.utility.
    inner = jnp.minimum(1.0, 1.0 / (1.0 + 1.0 / (ratio * ratio * ratio)))
    sigmoid = jnp.where(
        e <= d,
        0.0,
        jnp.where(
            d <= 0,
            1.0,
            jnp.where(x >= 1.0, 1.0, jnp.where(x <= 0.0, 0.0, inner)),
        ),
    )
    return jnp.where(
        pen_id == 0, step, jnp.where(pen_id == 1, linear, jnp.where(pen_id == 2, sigmoid, 0.0))
    )


def _touch_residency(res, gid, sizes, cap):
    """Compiled form of ``residency.touch_lru_array`` — ONE LRU slot-vector
    update per model load, threaded functionally through the scans.

    ``res`` is a (K,) id vector (LRU oldest first, -1 empty, empties
    packed at the tail); ``sizes`` maps id -> effective bytes and ``cap``
    is the byte budget (``residency.single_slot_encoding`` — unit sizes,
    cap 0 — folds the capacity-``None`` single-slot model into the same
    rule).  Returns (new_res, was_resident).
    """
    import jax.numpy as jnp

    was = (res == gid).any()
    removed = (res == gid) | (res < 0)
    order = jnp.argsort(removed, stable=True)  # keepers first, order kept
    kept = jnp.where(removed, -1, res)[order]
    lru = kept.at[(~removed).sum()].set(gid)  # gid at the MRU tail
    szs = jnp.where(lru >= 0, sizes[jnp.maximum(lru, 0)], 0.0)
    # Eviction only accompanies a LOAD (a resident touch is a pure MRU
    # reorder); the host loop evicts entry i iff evictable and the
    # running total still exceeds capacity when the scan arrives there.
    evictable = (lru >= 0) & (lru != gid) & ~was
    freed_before = jnp.cumsum(jnp.where(evictable, szs, 0.0)) - jnp.where(
        evictable, szs, 0.0
    )
    evict = evictable & (szs.sum() - freed_before > cap)
    keep = (lru >= 0) & ~evict
    return jnp.where(keep, lru, -1)[jnp.argsort(~keep, stable=True)], was


def _sequential_mean(tile, mask, size, axis):
    """Masked member mean with the SCALAR summation order (``total += u``
    member by member) — not an XLA tree reduce — so near-tied group
    utilities agree bit-for-bit with the host paths.  The member count is
    static under jit: small batches unroll to straight-line adds, large
    ones fall back to a fori_loop (same order, bounded program size).
    """
    import jax
    import jax.numpy as jnp

    b_max = tile.shape[axis]
    take = (lambda j: tile[:, j]) if axis == 1 else (lambda j: tile[j])
    zero = jnp.zeros_like(take(0))
    if b_max <= 64:
        s = zero
        for j in range(b_max):
            s = s + take(j) * mask[j]
        return s / size
    s = jax.lax.fori_loop(0, b_max, lambda j, acc: acc + take(j) * mask[j], zero)
    return s / size


def _chunk_member_mean(tile, mask, size):
    """Batched form of ``_sequential_mean`` for a leading chunk axis:
    masked member mean over axis -2 of a (..., B, M) tile with the SCALAR
    summation order (member by member, masked members contributing exact
    zero adds), so each chunk row reduces bit-for-bit like the sequential
    program's per-step mean.  ``mask``/``size`` must already broadcast
    against the tile with the member axis at -1/-(absent)."""
    import jax
    import jax.numpy as jnp

    b_max = tile.shape[-2]
    zero = jnp.zeros_like(tile[..., 0, :])
    if b_max <= 64:
        s = zero
        for j in range(b_max):
            s = s + tile[..., j, :] * mask[..., j, None]
        return s / size[..., None]
    s = jax.lax.fori_loop(
        0, b_max, lambda j, acc: acc + tile[..., j, :] * mask[..., j, None], zero
    )
    return s / size[..., None]


def _spec_select(chunk, res_mode, n_total, t, res, sizes, cap, tabs, score,
                 fixed_sel=None):
    """Speculate-K/validate/fallback selection over a single carry — the
    chunked core shared by the per-request and grouped programs.

    The sequential scans exist because every Eq. 13 decision moves the
    carry (queue-tail time ``t``, residency ``res``).  This driver
    amortizes that dependence the way speculative decoding amortizes
    autoregression.  ``tabs`` holds per-position tables padded to
    ``n_total + chunk`` rows (``fastpath.chunk_layout``): "swap" / "lat"
    / "gid" / "valid" model rows plus whatever ``score`` consumes.  Each
    round of the while loop:

      1. SPECULATE — score all ``chunk`` positions against the carry
         FROZEN at the chunk boundary: ONE batched utility tile instead
         of ``chunk`` sequential tiles.  (``fixed_sel`` names a table of
         precomputed decisions and skips this pass entirely — MaxAcc's
         selection is carry-independent.)
      2. RECONSTRUCT — the sequential carries the speculated decisions
         imply: a ``chunk``-step scalar chain keeping the scan's exact
         float association ``(t + swap) + lat`` (plus the compiled LRU
         slot updates in "lru" mode) — cheap, no utility tiles.
      3. VALIDATE — re-decide all positions under the reconstructed
         carries with a second batched tile.  Position k's carry is
         exact iff every speculated decision before k matched, so the
         accepted prefix runs through the FIRST conflict — inclusive:
         the conflicting position's own carry is still exact, so its
         validation decision is final (speculative decoding's bonus
         token).
      4. FALLBACK — advance by the accepted prefix only; the next round
         re-speculates from the first stale position under its now-
         exact carry.  Every round accepts >= 1 decision, so the loop
         ends within ``n_total`` rounds (exactly ``ceil(n/chunk)`` when
         nothing conflicts).

    Returns ``(sel, starts, lats, stats)`` (stats = stacked int64[2]
    ``[rounds, conflicts]``, one transfer) over the real
    ``n_total`` positions.  Bit-identical to the sequential scan by
    induction: accepted positions' carries are exact, and their
    validation decisions/outputs use the same elementwise float
    associations, first-max argmax and residency rule as the scan step.
    """
    import jax
    import jax.numpy as jnp

    n_pad = tabs["gid"].shape[0]  # n_total + chunk (fastpath.chunk_layout)

    def pick(tab, j):
        return jnp.take_along_axis(tab, j[:, None], axis=1)[:, 0]

    def decide(sl, tb, res_rep):
        # One batched Eq. 13 tile: the scan step's candidate scoring for
        # all chunk positions at once.  ``tb`` broadcasts the queue-tail
        # time per position, ``res_rep`` the residency per position;
        # (t + swap) + lat is the scan step's float association,
        # elementwise.
        swap_eff = jnp.where(res_rep, 0.0, sl["swap"])
        comp = (tb + swap_eff) + sl["lat"]
        u = score(sl, comp)
        return jnp.argmax(jnp.where(sl["valid"], u, -jnp.inf), axis=1), swap_eff

    def body(carry):
        p, t, res, osel, ostart, olat, rounds, conflicts = carry
        sl = {
            k: jax.lax.dynamic_slice_in_dim(v, p, chunk, axis=0)
            for k, v in tabs.items()
        }

        # 1. Speculate under the frozen boundary carry.
        if fixed_sel is not None:
            j_spec = sl[fixed_sel]
        else:
            if res_mode == "slot1":
                res_rep0 = sl["gid"] == res
            else:
                res_rep0 = (sl["gid"][:, :, None] == res[None, None, :]).any(-1)
            j_spec, _ = decide(sl, t, res_rep0)
        swap_sel = pick(sl["swap"], j_spec)
        lat_sel = pick(sl["lat"], j_spec)
        gid_sel = pick(sl["gid"], j_spec)

        # 2. Reconstruct the implied sequential carries (scalar chain).
        if res_mode == "slot1":
            res_states = jnp.concatenate([res[None], gid_sel[:-1]])
            sw_chain = jnp.where(gid_sel == res_states, 0.0, swap_sel)

            def tstep(tc, x):
                sw, lt = x
                return (tc + sw) + lt, tc

            _, t_vec = jax.lax.scan(
                tstep, t, (sw_chain, lat_sel), unroll=_UNROLL["chunk_chain"]
            )
        else:

            def rstep(c, x):
                tc, rc = c
                gk, sk, lk = x
                sw = jnp.where((rc == gk).any(), 0.0, sk)
                rn, _ = _touch_residency(rc, gk, sizes, cap)
                return ((tc + sw) + lk, rn), (tc, rc)

            _, (t_vec, res_states) = jax.lax.scan(
                rstep, (t, res), (gid_sel, swap_sel, lat_sel),
                unroll=_UNROLL["chunk_chain"],
            )

        # 3. Validate under the reconstructed carries.
        if res_mode == "slot1":
            res_rep = sl["gid"] == res_states[:, None]
        else:
            res_rep = (sl["gid"][:, :, None] == res_states[:, None, :]).any(-1)
        if fixed_sel is not None:
            j_true = j_spec
            swap_eff = jnp.where(res_rep, 0.0, sl["swap"])
        else:
            j_true, swap_eff = decide(sl, t_vec[:, None], res_rep)
        comp_fin = (t_vec + pick(swap_eff, j_true)) + pick(sl["lat"], j_true)

        # 4. Accept through the first conflict (inclusive: its carry was
        # still exact), clamped to the real positions left — padded rows
        # always match (all-(-inf) utilities, argmax 0 in both passes)
        # and can never be accepted past the clamp.
        mism = j_true != j_spec
        any_m = mism.any()
        first = jnp.argmax(mism).astype(p.dtype)
        a = jnp.minimum(jnp.where(any_m, first + 1, chunk), n_total - p)

        osel = jax.lax.dynamic_update_slice_in_dim(
            osel, j_true.astype(osel.dtype), p, 0
        )
        ostart = jax.lax.dynamic_update_slice_in_dim(ostart, t_vec, p, 0)
        olat = jax.lax.dynamic_update_slice_in_dim(olat, comp_fin - t_vec, p, 0)

        # Next boundary carry: the last ACCEPTED true decision applied to
        # its (exact) pre-state.
        t_next = comp_fin[a - 1]
        g_last = pick(sl["gid"], j_true)[a - 1]
        if res_mode == "slot1":
            res_next = g_last
        else:
            res_next, _ = _touch_residency(res_states[a - 1], g_last, sizes, cap)
        return (p + a, t_next, res_next, osel, ostart, olat,
                rounds + 1, conflicts + any_m.astype(conflicts.dtype))

    init = (
        jnp.asarray(0, jnp.int64),
        jnp.asarray(t, jnp.float64),
        jnp.asarray(res),
        jnp.zeros(n_pad, jnp.int64),
        jnp.zeros(n_pad, jnp.float64),
        jnp.zeros(n_pad, jnp.float64),
        jnp.asarray(0, jnp.int64),
        jnp.asarray(0, jnp.int64),
    )
    out = jax.lax.while_loop(lambda c: c[0] < n_total, body, init)
    _, _, _, osel, ostart, olat, rounds, conflicts = out
    # Stacked stats -> one device->host transfer on the caller side.
    return (osel[:n_total], ostart[:n_total], olat[:n_total],
            jnp.stack([rounds, conflicts]))


def _per_request_program(key, ordering, selection, data_aware, app_static, res_mode,
                         chunk=0):
    """One fused jitted program: Eq. 9/12 -> ordering -> Eq. 2/13 scan.

    ``app_static`` is a tuple of (num_models, has_theta) per application —
    the static branch structure; everything else is traced.  The scan
    carries (queue-tail time, residency): ``res_mode`` statically picks
    the carry — ``"slot1"`` (a single resident id: the paper's
    conservative swap-on-every-change default, cheapest per step) or
    ``"lru"`` (fixed-size LRU slot vectors updated by the compiled
    ``residency.touch_lru_array`` form — capacity-aware multi-model
    residency, the single-slot encoding included).
    """
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    def program(t0, res0, sizes, cap, deadlines, arrivals, rids, app_id,
                swap_tab, lat1_tab, gid_tab, valid_tab, pen_tab, per_app):
        n_total = deadlines.shape[0]
        m_max = swap_tab.shape[1]
        prio = jnp.zeros(n_total, dtype=jnp.float64)
        acc = jnp.zeros((n_total, m_max), dtype=jnp.float64)
        for (m_a, has_theta), (theta, trows, idx, d_rel, recalls, prof, sc, pref) in zip(
            app_static, per_app
        ):
            n_a = idx.shape[0]
            a_mat = jnp.tile(prof, (n_a, 1))
            if data_aware and has_theta:
                sharpened = theta @ recalls.T  # Eq. 9, batched
                sharpened = jnp.where(sc[None, :], prof[None, :], sharpened)
                a_mat = a_mat.at[trows].set(sharpened)
            var = a_mat.var(axis=1) if m_a > 1 else jnp.zeros(n_a)
            prio = prio.at[idx].set((1.0 + var) * jnp.exp(-jnp.maximum(d_rel, -60.0)))
            cols = jnp.arange(m_a)
            acc = acc.at[idx[:, None], cols[None, :]].set(a_mat[:, pref])

        if ordering == "fcfs":
            order = jnp.lexsort((rids, arrivals))
        elif ordering == "edf":
            order = jnp.lexsort((rids, deadlines))
        else:  # priority (Eq. 12)
            order = jnp.lexsort((rids, -prio))

        if selection == "max_accuracy":
            # Deadline-oblivious whole-window argmax tile; columns are in
            # tie-preference order so first-max == the scalar tie-break.
            sel_all = jnp.argmax(
                jnp.where(valid_tab[app_id], acc, -jnp.inf), axis=1
            )

        if chunk:
            # Speculative chunked selection: reorder the per-position
            # tables up front (the scan gathers per step instead) and pad
            # chunk inert rows (fastpath.chunk_layout's encoding).
            aid_o = app_id[order]

            def padr(x, cv=0):
                return jnp.pad(
                    x, [(0, chunk)] + [(0, 0)] * (x.ndim - 1), constant_values=cv
                )

            tabs = {
                "acc": padr(acc[order]),
                "dl": padr(deadlines[order], 1.0),
                "pen": padr(pen_tab[aid_o]),
                "swap": padr(swap_tab[aid_o]),
                "lat": padr(lat1_tab[aid_o]),
                "gid": padr(gid_tab[aid_o], -2),
                "valid": padr(valid_tab[aid_o]),
            }
            fixed = None
            if selection == "max_accuracy":
                tabs["sel"] = padr(sel_all[order])
                fixed = "sel"

            def score(sl, comp):
                gam = _penalty_jnp(sl["pen"][:, None], sl["dl"][:, None], comp)
                return sl["acc"] * (1.0 - jnp.clip(gam, 0.0, 1.0))

            sel, starts, lats, stats = _spec_select(
                chunk, res_mode, n_total, t0, res0, sizes, cap, tabs, score, fixed
            )
            return order, sel, starts, lats, stats

        def step(carry, g):
            t, res = carry
            aid = app_id[g]
            gid_row = gid_tab[aid]
            if res_mode == "slot1":
                is_res = gid_row == res
            else:
                is_res = (gid_row[:, None] == res[None, :]).any(axis=-1)
            swap_row = jnp.where(is_res, 0.0, swap_tab[aid])
            lat_row = lat1_tab[aid]
            if selection == "locally_optimal":
                # Eq. 13 at the queue tail: every candidate scored at once.
                completion = t + swap_row + lat_row
                gam = _penalty_jnp(pen_tab[aid], deadlines[g], completion)
                u = acc[g] * (1.0 - jnp.clip(gam, 0.0, 1.0))
                j = jnp.argmax(jnp.where(valid_tab[aid], u, -jnp.inf))
            else:
                j = sel_all[g]
            # (t + swap) + l(m, 1): the fast path's queue-tail association.
            comp = t + swap_row[j] + lat_row[j]
            if res_mode == "slot1":
                res = gid_row[j]
            else:
                res, _ = _touch_residency(res, gid_row[j], sizes, cap)
            return (comp, res), (j, t, comp - t)

        _, (sel, starts, lats) = jax.lax.scan(
            step, (t0, res0), order, unroll=_UNROLL["per_request"]
        )
        return order, sel, starts, lats

    prog = jax.jit(program)
    _PROGRAMS[key] = prog
    return prog


def _grouped_program(res_mode, chunk=0):
    """Jitted scan over ordered groups: one greedy Eq. 13 tile per step.
    ``res_mode`` statically picks the residency carry ("slot1" | "lru"),
    exactly as in ``_per_request_program``; ``chunk`` > 0 swaps the scan
    for the speculative chunked driver (``_spec_select``)."""
    key = ("grouped", res_mode, chunk)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    def program(t0, res0, gsizes, cap, acc, member_mask, deadlines, sizes,
                lat_tab, swap_tab, gid_tab, valid_tab, pen_tab):
        if chunk:

            def padr(x, cv=0):
                return jnp.pad(
                    x, [(0, chunk)] + [(0, 0)] * (x.ndim - 1), constant_values=cv
                )

            tabs = {
                "acc": padr(acc),
                "mask": padr(member_mask),
                "dl": padr(deadlines, 1.0),
                # Pad sizes/deadlines with 1.0 so inert rows divide and
                # penalize cleanly (their utilities mask to -inf anyway).
                "size": padr(sizes, 1.0),
                "pen": padr(pen_tab),
                "swap": padr(swap_tab),
                "lat": padr(lat_tab),
                "gid": padr(gid_tab, -2),
                "valid": padr(valid_tab),
            }

            def score(sl, comp):
                gam = _penalty_jnp(
                    sl["pen"][:, None, None], sl["dl"][:, :, None], comp[:, None, :]
                )
                tile = sl["acc"] * (1.0 - jnp.clip(gam, 0.0, 1.0))
                return _chunk_member_mean(tile, sl["mask"], sl["size"])

            return _spec_select(
                chunk, res_mode, acc.shape[0], t0, res0, gsizes, cap, tabs, score
            )

        def step(carry, g):
            t, res = carry
            gid_row = gid_tab[g]
            if res_mode == "slot1":
                is_res = gid_row == res
            else:
                is_res = (gid_row[:, None] == res[None, :]).any(axis=-1)
            swap_row = jnp.where(is_res, 0.0, swap_tab[g])
            # lat_tab is the host-precomputed l(m, b) per group: the
            # completion keeps peek_batch's (t + swap) + l(m, b) float
            # association (adds only — no FMA re-rounding on device).
            completion = t + swap_row + lat_tab[g]
            gam = _penalty_jnp(pen_tab[g], deadlines[g][:, None], completion[None, :])
            tile = acc[g] * (1.0 - jnp.clip(gam, 0.0, 1.0))  # (B_max, M_max)
            u_mean = _sequential_mean(tile, member_mask[g], sizes[g], axis=0)
            j = jnp.argmax(jnp.where(valid_tab[g], u_mean, -jnp.inf))
            comp = t + swap_row[j] + lat_tab[g, j]
            if res_mode == "slot1":
                res = gid_row[j]
            else:
                res, _ = _touch_residency(res, gid_row[j], gsizes, cap)
            return (comp, res), (j, t, comp - t)

        n_groups = acc.shape[0]
        _, (sel, starts, lats) = jax.lax.scan(
            step, (t0, res0), jnp.arange(n_groups), unroll=_UNROLL["grouped"]
        )
        return sel, starts, lats

    prog = jax.jit(program)
    _PROGRAMS[key] = prog
    return prog


def _multiworker_program(res_mode, chunk=0):
    """Compiled Eq. 15 placement: a jitted scan over the priority-ordered
    groups whose body scores the full (worker, model) utility tile, picks
    the argmax under the shared tie-break (utility, -scaled latency,
    name, -wid) via the precomputed preference permutation, and threads
    the per-worker busy-until times and LRU residency slots functionally.
    One generic program serves every pool: the pool/app structure is data
    (jit re-specializes on shapes only); ``res_mode`` statically picks
    the per-worker residency carry ("slot1" | "lru").

    ``chunk`` > 0 runs the speculate-K/validate/fallback rounds of
    ``_spec_select`` over the POOL carry (per-worker busy-until vector +
    per-worker residency): the speculation/validation tiles grow a
    leading chunk axis to (K, W, B, M), the flattened (worker, model)
    pick goes through the per-group preference permutation row-wise (the
    same first-max tie-break), and the reconstruction chain replays the
    speculated picks through ``t.at[wi].set`` / per-worker residency
    touches — the per-worker carry permits exactly the same accepted-
    prefix induction as the single-worker driver.
    """
    key = ("multiworker", res_mode, chunk)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    def program(t0, res0, wsizes, cap, acc, member_mask, deadlines, bsizes,
                app_id, lat_tab, sswap, gid_tab, valid_tab, pen_tab, pref_tab):
        m_max = gid_tab.shape[1]
        if chunk:
            return _spec_select_mw(
                chunk, res_mode, t0, res0, wsizes, cap, acc, member_mask,
                deadlines, bsizes, app_id, lat_tab, sswap, gid_tab, valid_tab,
                pen_tab, pref_tab,
            )

        def step(carry, g):
            t, res = carry
            aid = app_id[g]
            gid_row = gid_tab[aid]
            # (W, M): is model m resident on worker w?
            if res_mode == "slot1":
                is_res = res[:, None] == gid_row[None, :]
            else:
                is_res = (res[:, None, :] == gid_row[None, :, None]).any(axis=-1)
            swap_eff = jnp.where(is_res, 0.0, sswap[aid])
            # lat_tab holds the host-precomputed scaled l(m, b) per group,
            # so completions carry the exact peek_batch association
            # (t + swap) + l(m, b) — adds only, no FMA re-rounding.
            completion = t[:, None] + swap_eff + lat_tab[g]
            gam = _penalty_jnp(
                pen_tab[aid], deadlines[g][None, :, None], completion[:, None, :]
            )
            tile = acc[g][None, :, :] * (1.0 - jnp.clip(gam, 0.0, 1.0))  # (W, B, M)
            u_mean = _sequential_mean(tile, member_mask[g], bsizes[g], axis=1)
            u_flat = jnp.where(valid_tab[aid][None, :], u_mean, -jnp.inf).ravel()
            # First max over the preference permutation == the scalar
            # tie-break key (u, -scaled latency, name, -wid).
            p = pref_tab[aid]
            pick = p[jnp.argmax(u_flat[p])]
            wi, mi = pick // m_max, pick % m_max
            start = t[wi]
            comp = start + swap_eff[wi, mi] + lat_tab[g, wi, mi]
            if res_mode == "slot1":
                res = res.at[wi].set(gid_row[mi])
            else:
                res_w, _ = _touch_residency(res[wi], gid_row[mi], wsizes[wi], cap)
                res = res.at[wi].set(res_w)
            return (t.at[wi].set(comp), res), (wi, mi, start, comp - start)

        n_groups = acc.shape[0]
        _, (wsel, sel, starts, lats) = jax.lax.scan(
            step, (t0, res0), jnp.arange(n_groups), unroll=_UNROLL["multiworker"]
        )
        return wsel, sel, starts, lats

    prog = jax.jit(program)
    _PROGRAMS[key] = prog
    return prog


def _spec_select_mw(chunk, res_mode, t0, res0, wsizes, cap, acc, member_mask,
                    deadlines, bsizes, app_id, lat_tab, sswap, gid_tab,
                    valid_tab, pen_tab, pref_tab):
    """The multi-worker form of ``_spec_select``: speculate-K/validate/
    fallback over the POOL carry (per-worker busy-until times + per-
    worker residency).  Same induction, same bit-exactness argument —
    only the carry, the (K, W, B, M) tiles and the flattened
    (worker, model) pick differ from the single-worker driver."""
    import jax
    import jax.numpy as jnp

    m_max = gid_tab.shape[1]
    n_total = acc.shape[0]
    kk = jnp.arange(chunk)

    def padr(x, cv=0):
        return jnp.pad(x, [(0, chunk)] + [(0, 0)] * (x.ndim - 1), constant_values=cv)

    tabs = {
        "acc": padr(acc),
        "mask": padr(member_mask),
        "dl": padr(deadlines, 1.0),
        "bsize": padr(bsizes, 1.0),
        "lat": padr(lat_tab),
        "sswap": padr(sswap[app_id]),
        "gid": padr(gid_tab[app_id], -2),
        "valid": padr(valid_tab[app_id]),
        "pen": padr(pen_tab[app_id]),
        "pref": padr(pref_tab[app_id]),
    }
    n_pad = n_total + chunk

    def decide(sl, tb, res_rep):
        # (K, W, M) effective swaps/completions, (K, W, B, M) Eq. 13
        # tiles reduced by the scalar-order member mean, then the
        # row-wise first-max pick over the preference permutation —
        # exactly the sequential step's ops with a leading chunk axis.
        swap_eff = jnp.where(res_rep, 0.0, sl["sswap"])
        comp = (tb + swap_eff) + sl["lat"]
        gam = _penalty_jnp(
            sl["pen"][:, None, None, None],
            sl["dl"][:, None, :, None],
            comp[:, :, None, :],
        )
        tile = sl["acc"][:, None, :, :] * (1.0 - jnp.clip(gam, 0.0, 1.0))
        u_mean = _chunk_member_mean(tile, sl["mask"][:, None, :], sl["bsize"][:, None])
        u_flat = jnp.where(
            sl["valid"][:, None, :], u_mean, -jnp.inf
        ).reshape(chunk, -1)
        u_pref = jnp.take_along_axis(u_flat, sl["pref"], axis=1)
        idx = jnp.argmax(u_pref, axis=1)
        picks = jnp.take_along_axis(sl["pref"], idx[:, None], axis=1)[:, 0]
        return picks, swap_eff

    def body(carry):
        p, t, res, owsel, osel, ostart, olat, rounds, conflicts = carry
        sl = {
            k: jax.lax.dynamic_slice_in_dim(v, p, chunk, axis=0)
            for k, v in tabs.items()
        }

        # 1. Speculate under the frozen boundary pool state.
        if res_mode == "slot1":
            res_rep0 = res[None, :, None] == sl["gid"][:, None, :]
        else:
            res_rep0 = (
                res[None, :, None, :] == sl["gid"][:, None, :, None]
            ).any(-1)
        pick_s, _ = decide(sl, t[None, :, None], res_rep0)
        wi_s, mi_s = pick_s // m_max, pick_s % m_max
        gid_s = jnp.take_along_axis(sl["gid"], mi_s[:, None], axis=1)[:, 0]
        sw_s = sl["sswap"][kk, wi_s, mi_s]
        lt_s = sl["lat"][kk, wi_s, mi_s]

        # 2. Reconstruct the implied pool states (scalar chain).
        def rstep(c, x):
            tc, rc = c
            wk, gk, sk, lk = x
            if res_mode == "slot1":
                was = rc[wk] == gk
            else:
                was = (rc[wk] == gk).any()
            comp = (tc[wk] + jnp.where(was, 0.0, sk)) + lk
            if res_mode == "slot1":
                rn = rc.at[wk].set(gk)
            else:
                rw, _ = _touch_residency(rc[wk], gk, wsizes[wk], cap)
                rn = rc.at[wk].set(rw)
            return (tc.at[wk].set(comp), rn), (tc, rc)

        _, (t_states, res_states) = jax.lax.scan(
            rstep, (t, res), (wi_s, gid_s, sw_s, lt_s),
            unroll=_UNROLL["chunk_chain"],
        )

        # 3. Validate under the reconstructed pool states.
        if res_mode == "slot1":
            res_rep = res_states[:, :, None] == sl["gid"][:, None, :]
        else:
            res_rep = (
                res_states[:, :, :, None] == sl["gid"][:, None, None, :]
            ).any(-2)
        pick_t, swap_eff = decide(sl, t_states[:, :, None], res_rep)
        wi_t, mi_t = pick_t // m_max, pick_t % m_max
        gid_t = jnp.take_along_axis(sl["gid"], mi_t[:, None], axis=1)[:, 0]
        start_t = t_states[kk, wi_t]
        comp_fin = (start_t + swap_eff[kk, wi_t, mi_t]) + sl["lat"][kk, wi_t, mi_t]

        # 4. Accept through the first conflict (inclusive), clamped.
        mism = pick_t != pick_s
        any_m = mism.any()
        first = jnp.argmax(mism).astype(p.dtype)
        a = jnp.minimum(jnp.where(any_m, first + 1, chunk), n_total - p)

        owsel = jax.lax.dynamic_update_slice_in_dim(
            owsel, wi_t.astype(owsel.dtype), p, 0
        )
        osel = jax.lax.dynamic_update_slice_in_dim(
            osel, mi_t.astype(osel.dtype), p, 0
        )
        ostart = jax.lax.dynamic_update_slice_in_dim(ostart, start_t, p, 0)
        olat = jax.lax.dynamic_update_slice_in_dim(olat, comp_fin - start_t, p, 0)

        # Next boundary: the last ACCEPTED true pick applied to its
        # (exact) pre-state.
        wl = wi_t[a - 1]
        t_next = t_states[a - 1].at[wl].set(comp_fin[a - 1])
        res_last = res_states[a - 1]
        if res_mode == "slot1":
            res_next = res_last.at[wl].set(gid_t[a - 1])
        else:
            rw, _ = _touch_residency(res_last[wl], gid_t[a - 1], wsizes[wl], cap)
            res_next = res_last.at[wl].set(rw)
        return (p + a, t_next, res_next, owsel, osel, ostart, olat,
                rounds + 1, conflicts + any_m.astype(conflicts.dtype))

    init = (
        jnp.asarray(0, jnp.int64),
        jnp.asarray(t0, jnp.float64),
        jnp.asarray(res0),
        jnp.zeros(n_pad, jnp.int64),
        jnp.zeros(n_pad, jnp.int64),
        jnp.zeros(n_pad, jnp.float64),
        jnp.zeros(n_pad, jnp.float64),
        jnp.asarray(0, jnp.int64),
        jnp.asarray(0, jnp.int64),
    )
    out = jax.lax.while_loop(lambda c: c[0] < n_total, body, init)
    _, _, _, owsel, osel, ostart, olat, rounds, conflicts = out
    return (owsel[:n_total], osel[:n_total], ostart[:n_total], olat[:n_total],
            jnp.stack([rounds, conflicts]))


# --------------------------------------------------------------------------
# WindowPipeline
# --------------------------------------------------------------------------


class WindowPipeline:
    """Fused window data plane for one (apps, policy) configuration.

    ``run`` executes the full pipeline (ingest + schedule); ``schedule``
    assumes evidence/theta are already attached (streaming callers run
    the stochastic ingest exactly once per request).  Instances are cheap
    — compiled programs live in a module-level cache — so holding one
    per ``Simulation``/``EdgeServer`` reuses compilations across windows.
    """

    def __init__(
        self,
        apps: Mapping[str, Application],
        sneakpeeks=None,
        policy=None,
        backend: str | None = None,
        workers=None,
        chunk: int | None = None,
    ):
        """``workers`` (a sequence of ``multiworker.Worker``) switches the
        pipeline to the compiled Eq. 15 placement program: grouping /
        data-awareness / label-splitting come from the policy, placement
        from the (worker, model) utility tiles.

        ``chunk`` > 0 turns on speculative chunked selection (speculate-K
        /validate/fallback rounds instead of the sequential scan —
        bit-identical decisions, ``last_chunk_stats`` reports the
        conflict rate); ``None`` defers to the policy's ``chunk`` field,
        0 forces the sequential scan."""
        self.apps = apps
        self.sneakpeeks = sneakpeeks or {}
        self.policy = policy
        if backend is not None and backend not in ("auto", "jax", "numpy"):
            raise ValueError(f"unknown pipeline backend {backend!r}")
        self.backend = backend
        self.workers = list(workers) if workers else None
        if chunk is not None and int(chunk) < 0:
            raise ValueError(f"chunk must be >= 0, got {chunk}")
        self.chunk = chunk
        # Speculation stats of the LAST chunked schedule (None when the
        # sequential scan or the numpy backend ran): chunk, decisions,
        # rounds, conflicts, conflict_rate.
        self.last_chunk_stats: dict | None = None

    def _chunk_of(self, policy) -> int:
        c = self.chunk if self.chunk is not None else getattr(policy, "chunk", 0)
        c = int(c or 0)
        if c < 0:
            raise ValueError(f"chunk must be >= 0, got {c}")
        return c

    def _record_chunk_stats(self, chunk: int, decisions: int, stats) -> None:
        # One device->host transfer for both counters (int() per traced
        # scalar would sync twice).
        rounds, conflicts = np.asarray(stats, dtype=np.int64).tolist()
        self.last_chunk_stats = {
            "chunk": int(chunk),
            "decisions": int(decisions),
            "rounds": rounds,
            "conflicts": conflicts,
            "conflict_rate": conflicts / rounds if rounds else 0.0,
        }

    def resolved_backend(self) -> str:
        """The backend this pipeline will actually run ("jax" or "numpy")."""
        b = self.backend or _PIPELINE_BACKEND
        if b == "auto":
            b = "jax" if _have_jax() else "numpy"
        return b

    # -- stages ------------------------------------------------------------
    def ingest(self, requests: Sequence[Request]) -> None:
        """Batched SneakPeek stage (evidence + Dirichlet posterior)."""
        if self.sneakpeeks:
            ingest_window(requests, self.apps, self.sneakpeeks)

    def run(self, requests: Sequence[Request], now: float, policy=None, state=None) -> Schedule:
        """Full window pass: ingest then schedule."""
        self.ingest(requests)
        return self.schedule(requests, now, policy=policy, state=state)

    # -- scheduling --------------------------------------------------------
    def schedule(
        self,
        requests: Sequence[Request],
        now: float,
        policy=None,
        state=None,
        arrays: WindowArrays | None = None,
        workers=None,
        lat_scale=None,
        worker_mask=None,
    ) -> Schedule:
        """Schedule one window through the compiled programs (decision-
        identical to the numpy fast path; falls back to it on the numpy
        backend).  ``state`` seeds carried backlog/residency; ``workers``
        routes through the compiled Eq. 15 placement program.

        ``lat_scale`` ({(wid, model): s} drift corrections from
        ``core.health``) multiplies the compiled latency tables;
        ``worker_mask`` (a wid set) drops quarantined workers from the
        pool encoding before the placement scan — both multi-worker only
        (the single-worker programs have no pool to mask)."""
        policy = policy if policy is not None else self.policy
        if policy is None:
            raise ValueError("WindowPipeline needs a policy (init arg or call arg)")
        workers = workers if workers is not None else self.workers
        t0 = time.perf_counter()
        self.last_chunk_stats = None
        if not requests:
            return Schedule()
        if (lat_scale or worker_mask is not None) and not workers:
            raise ValueError("lat_scale/worker_mask require a multi-worker pipeline")
        backend = self.resolved_backend()
        if workers:
            if worker_mask is not None:
                workers = [w for w in workers if w.wid in worker_mask]
                if not workers:
                    raise ValueError("worker_mask excludes every worker")
            if backend == "numpy":
                sched = self._schedule_multiworker_numpy(
                    policy, requests, now, workers, state, arrays, lat_scale
                )
            else:
                sched = self._schedule_multiworker_jax(
                    policy, requests, now, workers, state, arrays, lat_scale
                )
        elif backend == "numpy":
            # The decision-identical numpy fast path.
            sched = self._schedule_numpy(policy, requests, now, state, arrays)
        elif policy.grouped:
            sched = self._schedule_grouped_jax(policy, requests, now, state, arrays)
        else:
            sched = self._schedule_per_request_jax(policy, requests, now, state, arrays)
        sched.chunk_stats = self.last_chunk_stats
        sched.scheduling_overhead_s = time.perf_counter() - t0
        return sched

    def _schedule_multiworker_numpy(self, policy, requests, now, workers, state,
                                    arrays, lat_scale=None):
        from repro.core.fastpath import fast_multiworker_schedule

        return fast_multiworker_schedule(
            requests, self.apps, workers, now,
            data_aware=policy.data_aware,
            split_by_label=policy.split_by_label,
            per_request=not policy.grouped,
            arrays=arrays,
            state=state,
            lat_scale=lat_scale,
        )

    def _schedule_numpy(self, policy, requests, now, state, arrays):
        if policy.grouped:
            return fast_grouped_schedule(
                requests, self.apps, now,
                tau=policy.tau,
                data_aware=policy.data_aware,
                split_by_label=policy.split_by_label,
                arrays=arrays,
                state=state,
            )
        return fast_per_request_schedule(
            requests, self.apps, now,
            ordering=policy.ordering,
            selection=policy.selection,
            data_aware=policy.data_aware,
            arrays=arrays,
            state=state,
        )

    def _state_seed(self, wa: WindowArrays, state, now: float):
        """Array-encoded single-worker seed for the compiled scans:
        (t0, residency carry, effective sizes, capacity, res_mode).  The
        same ``PoolArrays`` encoding the Eq. 15 path uses, restricted to
        worker 0 — capacity-based multi-model residency included (the
        former host-fast-path fallback is gone).  ``res_mode`` is the
        static program specialization: "slot1" (capacity-``None``
        semantics with at most one carried resident — a scalar id carry)
        or "lru" (the general slot-vector carry)."""
        from repro.core.fastpath import PoolArrays
        from repro.core.multiworker import Worker

        pool = PoolArrays.build([Worker(0)], wa, state=state, now=now)
        res_mode = pool.res_mode(state)
        res0 = np.int64(pool.res[0, 0]) if res_mode == "slot1" else pool.res[0]
        return (
            np.float64(pool.t[0]),
            res0,
            pool.sizes[0],
            np.float64(pool.capacity),
            res_mode,
        )

    def _global_ids(self, wa: WindowArrays) -> dict[str, int]:
        """Residency ids by model NAME (the timelines' residency key)."""
        gids: dict[str, int] = {}
        for app_name in wa.req_idx:
            for name in wa.app_arrays[app_name].names:
                gids.setdefault(name, len(gids))
        return gids

    def _window_tables(self, wa: WindowArrays):
        """Window-independent per-app model tables (tie-pref order),
        cached across windows with the same application set."""
        app_names = list(wa.req_idx)
        aas = [wa.app_arrays[n] for n in app_names]
        key = tuple(id(a) for a in aas)
        ent = _TABLES.get(key)
        if ent is not None:
            _TABLES[key] = _TABLES.pop(key)  # LRU touch
            return ent
        gids = self._global_ids(wa)
        n_apps = len(app_names)
        m_max = max(len(a.names) for a in aas)
        swap_tab = np.zeros((n_apps, m_max))
        lat1_tab = np.zeros((n_apps, m_max))
        gid_tab = np.full((n_apps, m_max), -2, dtype=np.int64)  # -2: never resident
        valid_tab = np.zeros((n_apps, m_max), dtype=bool)
        pen_tab = np.zeros(n_apps, dtype=np.int64)
        pref_tab = np.zeros((n_apps, m_max), dtype=np.int64)
        for ai, aa in enumerate(aas):
            pref = aa.tie_pref
            m = len(aa.names)
            swap_tab[ai, :m] = aa.swap[pref]
            lat1_tab[ai, :m] = aa.lat1[pref]
            gid_tab[ai, :m] = [gids[aa.names[int(i)]] for i in pref]
            valid_tab[ai, :m] = True
            pen_tab[ai] = _PENALTY_ID[aa.app.penalty]
            pref_tab[ai, :m] = pref
        ent = {
            "pin": aas,  # strong refs keep the id key sound
            "app_names": app_names,
            "gids": gids,
            "swap": swap_tab,
            "lat1": lat1_tab,
            "gid": gid_tab,
            "valid": valid_tab,
            "pen": pen_tab,
            "pref": pref_tab,
        }
        _TABLES[key] = ent
        while len(_TABLES) > _TABLES_MAX:
            _TABLES.pop(next(iter(_TABLES)))
        return ent

    def _jax_tables(self, tab):
        """Device-array versions of the window-independent per-app tables
        (and the per-app static Eq. 9 inputs), built once per table-cache
        entry under x64 so dtypes match the float64 programs — every
        subsequent window skips the host->device conversions."""
        jt = tab.get("jnp")
        if jt is not None:
            return jt
        import jax.numpy as jnp

        with self._enable_x64():
            jt = {
                k: jnp.asarray(tab[k]) for k in ("swap", "lat1", "gid", "valid", "pen")
            }
            jt["apps"] = {
                name: (
                    jnp.asarray(aa.R),
                    jnp.asarray(aa.profiled),
                    jnp.asarray(aa.sc),
                    jnp.asarray(aa.tie_pref),
                )
                for name, aa in zip(tab["app_names"], tab["pin"])
            }
        tab["jnp"] = jt
        return jt

    def _mw_tables(self, wa: WindowArrays, workers, pool):
        """Pool-scaled per-app model tables for the compiled Eq. 15
        program — (A, W, M_max) latency/swap tiles plus the flattened
        tie-break preference permutations — cached across windows per
        (application set, pool signature).  The per-app tables come from
        ``PoolArrays.app_table`` (padded to M_max here), so the scaling
        math and the tie-break rule have exactly one definition shared
        with the numpy fast path.  The drift-correction scales
        (``pool.lat_scale`` — already quantized by ``core.health``) are
        part of the cache key, so a converged EWMA reuses its tables
        while a still-moving one rebuilds them (bounded by the LRU)."""
        app_names = list(wa.req_idx)
        aas = [wa.app_arrays[n] for n in app_names]
        scale_key = (
            tuple(sorted((wid, name, float(s))
                         for (wid, name), s in pool.lat_scale.items()))
            if pool.lat_scale else None
        )
        key = (
            "mw",
            tuple(id(a) for a in aas),
            tuple((w.wid, w.speed, w.load_scale) for w in workers),
            scale_key,
        )
        ent = _TABLES.get(key)
        if ent is not None:
            _TABLES[key] = _TABLES.pop(key)  # LRU touch
            return ent
        from repro.core.fastpath import placement_pref

        n_apps = len(app_names)
        n_w = len(workers)
        m_max = max(len(a.names) for a in aas)
        speeds = np.array([w.speed for w in workers])
        slat_fixed = np.zeros((n_apps, n_w, m_max))
        slat_item = np.zeros((n_apps, n_w, m_max))
        sswap = np.zeros((n_apps, n_w, m_max))
        gid_tab = np.full((n_apps, m_max), -2, dtype=np.int64)  # -2: never resident
        valid_tab = np.zeros((n_apps, m_max), dtype=bool)
        pen_tab = np.zeros(n_apps, dtype=np.int64)
        pref_tab = np.zeros((n_apps, n_w * m_max), dtype=np.int64)
        for ai, name in enumerate(app_names):
            aa, a_fixed, a_item, a_swap, _pref, gid_row = pool.app_table(wa, name)
            m = len(aa.names)
            slat_fixed[ai, :, :m] = a_fixed
            slat_item[ai, :, :m] = a_item
            sswap[ai, :, :m] = a_swap
            gid_tab[ai, :m] = gid_row
            valid_tab[ai, :m] = True
            pen_tab[ai] = _PENALTY_ID[aa.app.penalty]
            # The shared Eq. 15 tie-break permutation, padded to m_max —
            # ranked by the same drift-corrected latencies as app_table.
            pref_tab[ai] = placement_pref(
                aa.names, aa.latency_s, speeds, pool.wids, pad_to=m_max,
                scale=pool.scale_matrix(aa),
            )
        ent = {
            "pin": aas,  # strong refs keep the id key sound
            "app_names": app_names,
            "m_max": m_max,
            "slat_fixed": slat_fixed,
            "slat_item": slat_item,
            "sswap": sswap,
            "gid": gid_tab,
            "valid": valid_tab,
            "pen": pen_tab,
            "pref": pref_tab,
        }
        _TABLES[key] = ent
        while len(_TABLES) > _TABLES_MAX:
            _TABLES.pop(next(iter(_TABLES)))
        return ent

    def _mw_setup(self, policy, requests, now, workers, state, arrays,
                  lat_scale=None):
        """Host-side half of the Eq. 15 path: grouping, ordering, pool
        encoding and the padded group tensors — everything up to (but not
        including) the compiled placement scan, shared verbatim with the
        sharded pipeline."""
        from repro.core.fastpath import PoolArrays
        from repro.core.grouping import group_by_app, split_groups_by_label

        acc_mode = "sharpened" if policy.data_aware else "profiled"
        if not policy.grouped:
            groups = {f"r{r.rid}": [r] for r in requests}
        else:
            groups = group_by_app(requests)
            if policy.split_by_label:
                groups = split_groups_by_label(groups, self.apps)

        # The Eq. 9/12 matrices feed the host-side assembly of the group
        # tensors either way, so the numpy WindowArrays (bit-identical to
        # the fast path's) beats a device round trip here; the compiled
        # program owns the placement scan itself.
        wa = arrays if arrays is not None else WindowArrays(requests, self.apps, now)

        prio = wa.priorities(policy.data_aware)
        member_idx = {key: wa.rows_of(members) for key, members in groups.items()}
        gp = {key: float(np.mean(prio[member_idx[key]])) for key in groups}  # Eq. 14
        # The fast path's multi-worker ordering rule, shared verbatim.
        ordered_groups = ordered_group_items(groups, gp, split_by_label=False)

        pool = PoolArrays.build(workers, wa, state=state, now=now, lat_scale=lat_scale)
        tab = self._mw_tables(wa, workers, pool)
        app_pos = {name: ai for ai, name in enumerate(tab["app_names"])}
        m_max = tab["m_max"]

        n_groups = len(ordered_groups)
        n_w = len(workers)
        b_max = max(len(members) for _, members in ordered_groups)
        acc = np.zeros((n_groups, b_max, m_max))
        member_mask = np.zeros((n_groups, b_max))
        deadlines = np.ones((n_groups, b_max))
        bsizes = np.zeros(n_groups)
        app_id = np.zeros(n_groups, dtype=np.int64)
        lat_tab = np.zeros((n_groups, n_w, m_max))
        acc_mats = {name: wa.acc_matrix(name, acc_mode) for name in wa.req_idx}
        for gi, (key, members) in enumerate(ordered_groups):
            app_name = members[0].app
            idx = member_idx[key]
            b = len(members)
            m = len(wa.app_arrays[app_name].names)
            ai = app_pos[app_name]
            acc[gi, :b, :m] = acc_mats[app_name][wa.row_of[idx]]
            member_mask[gi, :b] = 1.0
            deadlines[gi, :b] = wa.deadlines[idx]
            bsizes[gi] = float(b)
            app_id[gi] = ai
            # Scaled l(m, b) for this group, precomputed on the host so the
            # compiled completions match the numpy fast path bit-for-bit.
            lat_tab[gi] = tab["slat_fixed"][ai] + tab["slat_item"][ai] * b
        return {
            "wa": wa, "prio": prio, "member_idx": member_idx,
            "ordered_groups": ordered_groups, "pool": pool, "tab": tab,
            "acc": acc, "member_mask": member_mask, "deadlines": deadlines,
            "bsizes": bsizes, "app_id": app_id, "lat_tab": lat_tab,
        }

    def _mw_emit(self, setup, workers, wsel, sel, starts, lats):
        """Host-side emit of the Eq. 15 path: per-worker order counters +
        the fast path's member ordering rule, from the scan's outputs."""
        wa = setup["wa"]
        prio = setup["prio"]
        member_idx = setup["member_idx"]
        orders = {w.wid: 1 for w in workers}
        entries = []
        for gi, (key, members) in enumerate(setup["ordered_groups"]):
            aa = wa.app_arrays[members[0].app]
            idx = member_idx[key]
            w = workers[int(wsel[gi])]
            model = aa.names[int(sel[gi])]
            member_order = np.lexsort((wa.rids[idx], -prio[idx]))
            for j in member_order:
                entries.append(
                    ScheduleEntry(
                        request=wa.requests[int(idx[int(j)])],
                        model=model,
                        order=orders[w.wid],
                        worker=w.wid,
                        batch_id=gi,
                        est_start_s=float(starts[gi]),
                        est_latency_s=float(lats[gi]),
                    )
                )
                orders[w.wid] += 1
        sched = Schedule(entries=entries)
        sched.validate()
        return sched

    def _schedule_multiworker_jax(self, policy, requests, now, workers, state,
                                  arrays, lat_scale=None):
        setup = self._mw_setup(policy, requests, now, workers, state, arrays,
                               lat_scale)
        pool, tab = setup["pool"], setup["tab"]
        n_groups = len(setup["ordered_groups"])
        acc = setup["acc"]
        member_mask = setup["member_mask"]
        deadlines = setup["deadlines"]
        bsizes = setup["bsizes"]
        app_id = setup["app_id"]
        lat_tab = setup["lat_tab"]

        res_mode = pool.res_mode(state)
        res0 = pool.res[:, 0].copy() if res_mode == "slot1" else pool.res
        chunk = self._chunk_of(policy)
        prog = _multiworker_program(res_mode, chunk)
        with self._enable_x64():
            out = prog(
                pool.t, res0, pool.sizes, np.float64(pool.capacity),
                acc, member_mask, deadlines, bsizes, app_id,
                lat_tab, tab["sswap"], tab["gid"], tab["valid"], tab["pen"],
                tab["pref"],
            )
        if chunk:
            wsel, sel, starts, lats, stats = out
            self._record_chunk_stats(chunk, n_groups, stats)
        else:
            wsel, sel, starts, lats = out
        return self._mw_emit(
            setup, workers, np.asarray(wsel), np.asarray(sel),
            np.asarray(starts), np.asarray(lats),
        )

    def _enable_x64(self):
        import jax

        return jax.enable_x64(True)

    def _schedule_per_request_jax(self, policy, requests, now, state, arrays):
        if policy.selection not in ("locally_optimal", "max_accuracy"):
            raise ValueError(f"unknown selection {policy.selection!r}")
        if policy.ordering not in ("fcfs", "edf", "priority"):
            raise ValueError(f"unknown ordering {policy.ordering!r}")
        wa = arrays if arrays is not None else WindowArrays(requests, self.apps, now)
        tab = self._window_tables(wa)
        app_names = tab["app_names"]
        n_total = len(wa.requests)

        # Window-independent args live as committed device arrays in the
        # table cache (_window_tables) — passing jax.Arrays into the jitted
        # program skips the per-call host->device conversion that would
        # otherwise run for every table on every window.
        jt = self._jax_tables(tab)
        app_id = np.zeros(n_total, dtype=np.int64)
        per_app, app_static = [], []
        for ai, name in enumerate(app_names):
            aa = wa.app_arrays[name]
            idx = wa.req_idx[name]
            app_id[idx] = ai
            trows = wa._theta_rows[name]
            app_static.append((len(aa.names), bool(trows.size)))
            r_j, prof_j, sc_j, pref_j = jt["apps"][name]
            per_app.append((
                wa._theta_mat[name], trows, idx, wa.deadlines[idx] - float(now),
                r_j, prof_j, sc_j, pref_j,
            ))

        t0, res0, sizes0, cap, res_mode = self._state_seed(wa, state, now)
        chunk = self._chunk_of(policy)
        key = (
            "per_request", policy.ordering, policy.selection,
            bool(policy.data_aware), tuple(app_static), res_mode, chunk,
        )
        prog = _per_request_program(
            key, policy.ordering, policy.selection, bool(policy.data_aware),
            tuple(app_static), res_mode, chunk,
        )
        with self._enable_x64():
            out = prog(
                t0, res0, sizes0, cap, wa.deadlines, wa.arrivals,
                np.asarray(wa.rids, dtype=np.int64), app_id,
                jt["swap"], jt["lat1"], jt["gid"], jt["valid"], jt["pen"],
                per_app,
            )
        if chunk:
            order, sel, starts, lats, stats = out
            self._record_chunk_stats(chunk, n_total, stats)
        else:
            order, sel, starts, lats = out
        order = np.asarray(order)
        local = tab["pref"][app_id[order], np.asarray(sel)]
        # Host assembly off np scalars: bulk tolist() + local bindings —
        # this loop runs once per request and shows up in the gated
        # schedule-only bench cells, so keep it allocation-lean.
        order_l = order.tolist()
        local_l = local.tolist()
        starts_l = np.asarray(starts).tolist()
        lats_l = np.asarray(lats).tolist()
        requests = wa.requests
        app_of = wa.app_of
        names = {name: wa.app_arrays[name].names for name in wa.req_idx}

        # Positional construction: (request, model, order, worker,
        # batch_id, est_start_s, est_latency_s).
        entries = [
            ScheduleEntry(
                requests[g], names[app_of[g]][local_l[k]], k + 1, 0, -1,
                starts_l[k], lats_l[k],
            )
            for k, g in enumerate(order_l)
        ]
        sched = Schedule(entries=entries)
        sched.validate()
        return sched

    def _grouped_setup(self, policy, requests, now, state, arrays):
        """Host-side half of the grouped path: grouping, the brute-force
        branch (returned as ``{"sched": ...}`` when it applies), ordering
        and the padded group tensors + carry seed — shared verbatim with
        the sharded pipeline."""
        from repro.core.bruteforce import brute_force_groups
        from repro.core.evaluation import WorkerTimeline
        from repro.core.grouping import group_by_app, split_groups_by_label

        acc_mode = "sharpened" if policy.data_aware else "profiled"
        groups = group_by_app(requests)
        if policy.split_by_label:
            groups = split_groups_by_label(groups, self.apps)

        if arrays is not None:
            wa = arrays
        else:
            # Stacked Eq. 9/12 device program (float64 for decision parity).
            with self._enable_x64():
                (wa,) = precompute_windows(
                    [(list(requests), now)], self.apps,
                    data_aware=policy.data_aware, backend="jax",
                )

        if len(groups) <= policy.tau:
            if state is not None:
                tl = state.peek_timeline(0).clone()
                tl.advance(now)
            else:
                tl = WorkerTimeline(now)
            try:
                sched = brute_force_groups(
                    groups, self.apps, now, acc_mode=acc_mode, arrays=wa, timeline=tl
                )
                return {"sched": sched}
            except ValueError:
                pass  # too many candidates; fall through to the greedy scan

        prio = wa.priorities(policy.data_aware)
        member_idx = {key: wa.rows_of(members) for key, members in groups.items()}
        gp = {key: float(np.mean(prio[member_idx[key]])) for key in groups}  # Eq. 14
        ordered_groups = ordered_group_items(groups, gp, policy.split_by_label)

        gids = self._global_ids(wa)
        n_groups = len(ordered_groups)
        b_max = max(len(members) for _, members in ordered_groups)
        m_max = max(len(wa.app_arrays[n].names) for n in wa.req_idx)
        acc = np.zeros((n_groups, b_max, m_max))
        member_mask = np.zeros((n_groups, b_max))
        deadlines = np.ones((n_groups, b_max))
        sizes = np.zeros(n_groups)
        lat_tab = np.zeros((n_groups, m_max))
        swap_tab = np.zeros((n_groups, m_max))
        gid_tab = np.full((n_groups, m_max), -2, dtype=np.int64)
        valid_tab = np.zeros((n_groups, m_max), dtype=bool)
        pen_tab = np.zeros(n_groups, dtype=np.int64)
        prefs = []
        for gi, (key, members) in enumerate(ordered_groups):
            aa = wa.app_arrays[members[0].app]
            pref = aa.tie_pref
            prefs.append(pref)
            idx = member_idx[key]
            b, m = len(members), len(aa.names)
            a_rows = wa.acc_matrix(members[0].app, acc_mode)[wa.row_of[idx]]
            acc[gi, :b, :m] = a_rows[:, pref]
            member_mask[gi, :b] = 1.0
            deadlines[gi, :b] = wa.deadlines[idx]
            sizes[gi] = float(b)
            # Host-precomputed l(m, b) (batch_latency association).
            lat_tab[gi, :m] = (aa.lat_fixed + aa.lat_item * b)[pref]
            swap_tab[gi, :m] = aa.swap[pref]
            gid_tab[gi, :m] = [gids[aa.names[int(i)]] for i in pref]
            valid_tab[gi, :m] = True
            pen_tab[gi] = _PENALTY_ID[aa.app.penalty]

        seed = self._state_seed(wa, state, now)
        return {
            "sched": None, "wa": wa, "prio": prio, "member_idx": member_idx,
            "ordered_groups": ordered_groups, "prefs": prefs, "seed": seed,
            "acc": acc, "member_mask": member_mask, "deadlines": deadlines,
            "sizes": sizes, "lat_tab": lat_tab, "swap_tab": swap_tab,
            "gid_tab": gid_tab, "valid_tab": valid_tab, "pen_tab": pen_tab,
        }

    def _grouped_emit(self, setup, sel, starts, lats):
        """Host-side emit of the grouped path (single global order
        counter, model names through the tie-pref permutation)."""
        wa = setup["wa"]
        prio = setup["prio"]
        member_idx = setup["member_idx"]
        prefs = setup["prefs"]
        entries = []
        order = 1
        for gi, (key, members) in enumerate(setup["ordered_groups"]):
            aa = wa.app_arrays[members[0].app]
            idx = member_idx[key]
            model = aa.names[int(prefs[gi][int(sel[gi])])]
            member_order = np.lexsort((wa.rids[idx], -prio[idx]))
            for j in member_order:
                entries.append(
                    ScheduleEntry(
                        request=wa.requests[int(idx[int(j)])],
                        model=model,
                        order=order,
                        batch_id=gi,
                        est_start_s=float(starts[gi]),
                        est_latency_s=float(lats[gi]),
                    )
                )
                order += 1
        sched = Schedule(entries=entries)
        sched.validate()
        return sched

    def _schedule_grouped_jax(self, policy, requests, now, state, arrays):
        setup = self._grouped_setup(policy, requests, now, state, arrays)
        if setup.get("sched") is not None:  # brute-force branch (<= tau)
            return setup["sched"]
        t0, res0, gsizes, cap, res_mode = setup["seed"]
        n_groups = len(setup["ordered_groups"])
        chunk = self._chunk_of(policy)
        prog = _grouped_program(res_mode, chunk)
        with self._enable_x64():
            out = prog(
                t0, res0, gsizes, cap, setup["acc"], setup["member_mask"],
                setup["deadlines"], setup["sizes"], setup["lat_tab"],
                setup["swap_tab"], setup["gid_tab"], setup["valid_tab"],
                setup["pen_tab"],
            )
        if chunk:
            sel, starts, lats, stats = out
            self._record_chunk_stats(chunk, n_groups, stats)
        else:
            sel, starts, lats = out
        return self._grouped_emit(
            setup, np.asarray(sel), np.asarray(starts), np.asarray(lats)
        )


def pipeline_schedule(
    policy,
    requests: Sequence[Request],
    apps: Mapping[str, Application],
    now: float,
    state=None,
    arrays: WindowArrays | None = None,
    backend: str | None = None,
    workers=None,
    lat_scale=None,
    worker_mask=None,
    chunk: int | None = None,
    shard=None,
) -> Schedule:
    """One pipelined window pass for ``SchedulerPolicy.schedule`` /
    ``schedule_window`` (``workers`` selects the Eq. 15 placement
    program; ``lat_scale``/``worker_mask`` the closed-loop drift
    corrections and health masking — multi-worker only; ``chunk``
    overrides the policy's speculative chunked selection knob; ``shard``
    (or the policy's ``shard`` field) routes through the device-sharded
    ``core.shard.ShardedWindowPipeline`` — bit-identical decisions)."""
    shard = shard if shard is not None else getattr(policy, "shard", False)
    if shard:
        from repro.core.shard import ShardedWindowPipeline

        pipe = ShardedWindowPipeline(
            apps, policy=policy, backend=backend, workers=workers, chunk=chunk,
            shard=shard,
        )
    else:
        pipe = WindowPipeline(
            apps, policy=policy, backend=backend, workers=workers, chunk=chunk
        )
    return pipe.schedule(
        requests, now, state=state, arrays=arrays,
        lat_scale=lat_scale, worker_mask=worker_mask,
    )
