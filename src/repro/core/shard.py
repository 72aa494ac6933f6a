"""Sharded window scheduling: the compiled pipeline split across devices.

``ShardedWindowPipeline`` places the window's decision tables on a 1-D
``jax.sharding`` mesh (axis ``"shard"``, built through ``launch.mesh`` /
``distributed.sharding``) and computes the batched Eq. 2/13/15 utility
tiles per shard, resolving every global decision through exact all-reduce
collectives — while keeping each scheduling decision BIT-IDENTICAL to
the single-device pipeline (the repo's core invariant).  The split
follows what float arithmetic allows:

  * **Elementwise tile phases shard.**  The Eq. 2/13 utility tiles
    (penalties, products, masked member means) and the Eq. 15
    (worker, batch, model) tiles are elementwise along the sharded axis
    — request rows for the single-worker selectors, workers for the
    placement scan — so a shard computes exactly the rows the
    single-device program would, with the same per-row float
    associations.  Cutting the axis cannot change any row's bits.
  * **The Eq. 9 contraction stays replicated.**  ``theta @ R.T`` is a
    reduction whose rounding XLA is free to re-associate per SHAPE:
    row-sharding the gemm changes last-ulp results, which would break
    decision bit-identity on near-ties.  The sharded pipeline computes
    Eq. 9/12 at the reference shape (one replicated program) and shards
    only the downstream tiles.
  * **Argmaxes all-reduce exactly.**  The global Eq. 2/13 argmax over a
    sharded axis is comparisons only: each shard reduces its rows
    (first-max, same tie-preference column order), then a global max on
    the value and a global min on the tie-break rank pick the same
    winner the single-device first-max would — no float arithmetic
    crosses shards.  The global max/min gather every shard's value and
    reduce locally (``_all_max``/``_all_min``): a TPU all-reduces 64-bit
    values by sum only.
  * **The sequential carry reconciles replicated.**  Queue-tail time and
    LRU residency are inherently sequential; the sharded selector runs
    the speculate/validate rounds of ``pipeline._spec_select`` with the
    two batched tiles computed per shard and the scalar carry-
    reconstruction chain replicated on every shard (identical ops ->
    identical replicas; the per-round inputs arrive via exact
    ``all_gather``).  With ``chunk=K`` the rounds accept at most K
    decisions each — the same rounds, conflicts and decisions as the
    single-device chunked driver; with ``chunk=0`` one round speculates
    the whole remaining window (the ``chunk > window`` degenerate case
    already property-tested bit-identical to the sequential scan).

Single-worker policies shard the request axis; the multi-worker Eq. 15
placement shards the WORKER axis of its (worker, batch, model) tiles and
resolves each step's placement with the max/min all-reduce argmax
under the shared tie-break permutation (rank = position in
``fastpath.placement_pref`` — globally unique, so the reduce is exact).
Rows/workers padded up to a multiple of the shard count are encoded
inert (``valid=False`` -> ``-inf`` utilities, tie-rank ``+inf``): they
can never win an argmax, never enter a carry, and never emit a decision.

``shard=True`` uses every local device; ``shard=N`` uses N.  With one
shard every method delegates verbatim to ``WindowPipeline`` (same
compiled-program cache keys — a regression test asserts byte-identical
dispatch).  Wire-up: ``make_policy(name, shard=True)``,
``Simulation(shard=True)``, ``EdgeServer(shard=True)`` — composing with
``chunk=K`` speculation and ``overlap=True`` serving.
"""
from __future__ import annotations

import numpy as np

from repro.core.fastpath import WindowArrays
from repro.core.pipeline import (
    _PROGRAMS,
    _UNROLL,
    WindowPipeline,
    _chunk_member_mean,
    _penalty_jnp,
    _sequential_mean,
    _touch_residency,
)

__all__ = [
    "ShardedWindowPipeline",
    "resolve_num_shards",
    "shard_mesh",
    "row_specs",
    "pad_rows",
]

# Tie-break rank sentinel: larger than any real preference position, small
# enough that int64 min arithmetic never overflows.
_RANK_INF = np.int64(2**62)
# One (S,)-mesh per shard count, shared across pipelines (device order is
# stable within a process, so equal counts mean equal meshes).
_MESHES: dict = {}


def pad_rows(n: int, shards: int) -> int:
    """Rows after padding ``n`` up to a multiple of ``shards`` (>= 1 row
    per shard, so every device holds a block even for tiny windows)."""
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    blocks = max(1, -(-n // shards))
    return blocks * shards


def resolve_num_shards(shard) -> int:
    """Resolve the ``shard`` flag (bool | int) to a device count."""
    if shard is True:
        import jax

        return jax.local_device_count()
    n = int(shard)
    if n < 0:
        raise ValueError(f"shard must be True or >= 0, got {shard}")
    if n > 1:
        import jax

        avail = jax.local_device_count()
        if n > avail:
            raise ValueError(
                f"shard={n} exceeds the {avail} available device(s) "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "to force host devices)"
            )
    return max(n, 1)


def shard_mesh(num_shards: int):
    """The 1-D scheduling mesh (axis "shard") over the first N devices,
    built through ``launch.mesh.make_mesh`` and cached per count."""
    mesh = _MESHES.get(num_shards)
    if mesh is None:
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((num_shards,), ("shard",))
        _MESHES[num_shards] = mesh
    return mesh


def row_specs(mesh, shapes: dict, axis: dict | None = None):
    """PartitionSpecs for the decision tables via the distribution
    layer's divisibility-aware rule resolution: logical axis "req" maps
    to mesh axis "shard" (``axis`` overrides which dim is sharded, by
    table name; default 0)."""
    from repro.distributed.sharding import ShardingPolicy, spec_for_axes

    pol = ShardingPolicy(param_rules={"req": ["shard"]}, act_rules={})
    specs = {}
    for name, shape in shapes.items():
        dim = (axis or {}).get(name, 0)
        axes = tuple("req" if i == dim else None for i in range(len(shape)))
        specs[name] = spec_for_axes(axes, tuple(shape), pol, mesh)
    return specs


def _place(mesh, tabs: dict, specs: dict):
    """Commit host tables to the mesh under their specs (one transfer,
    so the jitted shard_map programs consume pre-placed blocks)."""
    import jax
    from repro.distributed.sharding import named_sharding_tree

    ns = named_sharding_tree(specs, mesh)
    return {k: jax.device_put(v, ns[k]) for k, v in tabs.items()}


def _all_max(jnp, jax, x):
    """Max over the "shard" axis, exact in any order: every shard's value
    is gathered and reduced locally (a TPU all-reduces 64-bit values by
    sum only)."""
    return jnp.max(jax.lax.all_gather(x, "shard"), axis=0)


def _all_min(jnp, jax, x):
    """Min over the "shard" axis (see ``_all_max``)."""
    return jnp.min(jax.lax.all_gather(x, "shard"), axis=0)


# --------------------------------------------------------------------------
# Sharded single-carry selection (per-request + grouped policies)
# --------------------------------------------------------------------------


def _sharded_select_program(kind, res_mode, num_shards, fixed):
    """Speculate/validate selection with request-sharded tiles.

    The same induction as ``pipeline._spec_select`` — each round
    speculates positions against the carry frozen at the round boundary,
    reconstructs the implied sequential carries, validates, and accepts
    through the first conflict — but the two batched utility tiles are
    computed per shard on that shard's row block, and the scalar
    reconstruction chain runs REPLICATED on every shard from the exact
    per-position picks (``all_gather`` — bit-exact copies).  The first
    conflict is a global min (``_all_min``) over global row indices.
    ``k_eff`` caps the accepted prefix per round: passing the policy's
    chunk reproduces the single-device chunked rounds (same conflicts,
    same stats); passing the window length speculates everything left
    (the proven ``chunk > window`` degenerate case of the sequential
    scan).  Inert padding rows (``valid=False``) decide identically in
    both passes and are clamped out of every accept window, so they
    never win an argmax and never reach a carry.
    """
    key = ("shard_select", kind, res_mode, num_shards, fixed)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = shard_mesh(num_shards)

    def take(tab, j):
        return jnp.take_along_axis(tab, j[:, None], axis=1)[:, 0]

    def score(sl, comp):
        # The chunked drivers' Eq. 13 tiles, verbatim (elementwise along
        # the row axis -> per-row bits independent of the block size).
        if kind == "grouped":
            gam = _penalty_jnp(
                sl["pen"][:, None, None], sl["dl"][:, :, None], comp[:, None, :]
            )
            tile = sl["acc"] * (1.0 - jnp.clip(gam, 0.0, 1.0))
            return _chunk_member_mean(tile, sl["mask"], sl["size"])
        gam = _penalty_jnp(sl["pen"][:, None], sl["dl"][:, None], comp)
        return sl["acc"] * (1.0 - jnp.clip(gam, 0.0, 1.0))

    def decide(sl, tb, res_rep):
        swap_eff = jnp.where(res_rep, 0.0, sl["swap"])
        comp = (tb + swap_eff) + sl["lat"]
        u = score(sl, comp)
        return jnp.argmax(jnp.where(sl["valid"], u, -jnp.inf), axis=1), swap_eff

    def fn(n_total, k_eff, t0, res0, sizes, cap, tabs):
        n_rows = tabs["gid"].shape[0]  # this shard's block
        n_pad = n_rows * num_shards
        off = jax.lax.axis_index("shard").astype(jnp.int64) * n_rows
        rows = off + jnp.arange(n_rows, dtype=jnp.int64)
        allrows = jnp.arange(n_pad, dtype=jnp.int64)

        def gather(x):
            return jax.lax.all_gather(x, "shard", axis=0, tiled=True)

        def body(carry):
            p, t, res, osel, ostart, olat, rounds, conflicts = carry
            active = (rows >= p) & (rows < p + k_eff) & (rows < n_total)

            # 1. SPECULATE: this shard's rows against the frozen carry.
            if fixed:
                j_spec = tabs["sel"]
            else:
                if res_mode == "slot1":
                    rep0 = tabs["gid"] == res
                else:
                    rep0 = (tabs["gid"][:, :, None] == res[None, None, :]).any(-1)
                j_spec, _ = decide(tabs, t, rep0)
            act_g = gather(active)
            sw_g = gather(take(tabs["swap"], j_spec))
            lt_g = gather(take(tabs["lat"], j_spec))
            gd_g = gather(take(tabs["gid"], j_spec))

            # 2. RECONSTRUCT the implied carries — replicated scalar
            # chain with the scan's exact (t + swap) + lat association;
            # rows outside the round window pass the carry through.
            if res_mode == "slot1":

                def rstep(c, x):
                    tc, rc = c
                    act, gk, sk, lk = x
                    sw = jnp.where(gk == rc, 0.0, sk)
                    tn = (tc + sw) + lk
                    return (jnp.where(act, tn, tc), jnp.where(act, gk, rc)), (tc, rc)

            else:

                def rstep(c, x):
                    tc, rc = c
                    act, gk, sk, lk = x
                    sw = jnp.where((rc == gk).any(), 0.0, sk)
                    rn, _ = _touch_residency(rc, gk, sizes, cap)
                    tn = (tc + sw) + lk
                    return (jnp.where(act, tn, tc), jnp.where(act, rn, rc)), (tc, rc)

            _, (t_vec, res_states) = jax.lax.scan(
                rstep, (t, res), (act_g, gd_g, sw_g, lt_g),
                unroll=_UNROLL["chunk_chain"],
            )

            # 3. VALIDATE: this shard's rows under its slice of the
            # reconstructed carries.
            t_l = jax.lax.dynamic_slice_in_dim(t_vec, off, n_rows)
            res_l = jax.lax.dynamic_slice_in_dim(res_states, off, n_rows)
            if res_mode == "slot1":
                rep = tabs["gid"] == res_l[:, None]
            else:
                rep = (tabs["gid"][:, :, None] == res_l[:, None, :]).any(-1)
            if fixed:
                j_true = j_spec
                swap_eff = jnp.where(rep, 0.0, tabs["swap"])
            else:
                j_true, swap_eff = decide(tabs, t_l[:, None], rep)
            jt_g = gather(j_true)
            swe_g = gather(take(swap_eff, j_true))
            ltt_g = gather(take(tabs["lat"], j_true))
            gdt_g = gather(take(tabs["gid"], j_true))
            comp_fin = (t_vec + swe_g) + ltt_g

            # 4. First conflict via all-reduce min over global rows;
            # accept through it (inclusive), capped at k_eff.
            mism = (j_true != j_spec) & active
            loc_first = jnp.min(jnp.where(mism, rows, _RANK_INF))
            first = _all_min(jnp, jax, loc_first)
            any_m = first < _RANK_INF
            a = jnp.where(any_m, first + 1 - p, jnp.minimum(k_eff, n_total - p))

            accept = (allrows >= p) & (allrows < p + a)
            osel = jnp.where(accept, jt_g, osel)
            ostart = jnp.where(accept, t_vec, ostart)
            olat = jnp.where(accept, comp_fin - t_vec, olat)

            last = p + a - 1
            t_next = comp_fin[last]
            g_last = gdt_g[last]
            if res_mode == "slot1":
                res_next = g_last
            else:
                res_next, _ = _touch_residency(res_states[last], g_last, sizes, cap)
            return (p + a, t_next, res_next, osel, ostart, olat,
                    rounds + 1, conflicts + any_m.astype(conflicts.dtype))

        init = (
            jnp.asarray(0, jnp.int64),
            jnp.asarray(t0, jnp.float64),
            jnp.asarray(res0),
            jnp.zeros(n_pad, jnp.int64),
            jnp.zeros(n_pad, jnp.float64),
            jnp.zeros(n_pad, jnp.float64),
            jnp.asarray(0, jnp.int64),
            jnp.asarray(0, jnp.int64),
        )
        out = jax.lax.while_loop(lambda c: c[0] < n_total, body, init)
        _, _, _, osel, ostart, olat, rounds, conflicts = out
        return osel, ostart, olat, jnp.stack([rounds, conflicts])

    tab_names = ["acc", "dl", "pen", "swap", "lat", "gid", "valid"]
    if kind == "grouped":
        tab_names += ["mask", "size"]
    if fixed:
        tab_names += ["sel"]
    tab_specs = {k: P("shard") for k in tab_names}
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(), tab_specs),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    prog = jax.jit(mapped)
    _PROGRAMS[key] = prog
    return prog


# --------------------------------------------------------------------------
# Sharded Eq. 15 placement (multi-worker) — worker-axis tiles
# --------------------------------------------------------------------------


def _pick_allreduce(jnp, jax, u_flat, rank_flat):
    """Exact global first-max under the preference permutation: local
    first-max (max utility, min rank among local ties), then a global max
    on the value and a global min on the rank — comparisons only, so the
    winner is bit-for-bit the single-device argmax over the permuted
    tile.  Works elementwise over any leading axes."""
    ub = jnp.max(u_flat, axis=-1)
    rb = jnp.min(jnp.where(u_flat == ub[..., None], rank_flat, _RANK_INF), axis=-1)
    u_star = _all_max(jnp, jax, ub)
    return _all_min(jnp, jax, jnp.where(ub == u_star, rb, _RANK_INF))


def _owner_bcast(jnp, jax, mine, val):
    """Broadcast the picking shard's float value (exact copy via a global
    max against -inf fillers)."""
    return _all_max(jnp, jax, jnp.where(mine, val, -jnp.inf))


def _sharded_mw_program(res_mode, num_shards):
    """Sharded sequential Eq. 15 placement: a scan over the ordered
    groups whose (worker, batch, model) utility tile is split along the
    WORKER axis — each shard scores its worker block (elementwise rows +
    the scalar-order member mean, bit-identical to the full tile's rows)
    — with the placement argmax resolved by the max/min all-reduce
    under the tie-break rank (the inverse ``placement_pref``
    permutation).  The pool carry (busy-until times + residency) is
    replicated: every shard applies the same winning update.  Workers
    padded up to the shard count are invalid (-inf utilities, +inf
    rank): they never win a placement."""
    key = ("shard_mw", res_mode, num_shards)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = shard_mesh(num_shards)

    def fn(t0, res0, wsizes, cap, w_valid, acc, member_mask, deadlines, bsizes,
           app_id, lat_tab, sswap, gid_tab, valid_tab, pen_tab, pref_rep,
           rank_tab):
        w_local = sswap.shape[1]
        m_max = gid_tab.shape[1]
        off = jax.lax.axis_index("shard").astype(jnp.int64) * w_local

        def step(carry, g):
            t, res = carry
            aid = app_id[g]
            gid_row = gid_tab[aid]
            t_l = jax.lax.dynamic_slice_in_dim(t, off, w_local)
            res_l = jax.lax.dynamic_slice_in_dim(res, off, w_local)
            if res_mode == "slot1":
                is_res = res_l[:, None] == gid_row[None, :]
            else:
                is_res = (res_l[:, None, :] == gid_row[None, :, None]).any(-1)
            swap_eff = jnp.where(is_res, 0.0, sswap[aid])
            completion = t_l[:, None] + swap_eff + lat_tab[g]
            gam = _penalty_jnp(
                pen_tab[aid], deadlines[g][None, :, None], completion[:, None, :]
            )
            tile = acc[g][None, :, :] * (1.0 - jnp.clip(gam, 0.0, 1.0))
            u_mean = _sequential_mean(tile, member_mask[g], bsizes[g], axis=1)
            u_flat = jnp.where(
                valid_tab[aid][None, :] & w_valid[:, None], u_mean, -jnp.inf
            ).ravel()
            r_star = _pick_allreduce(jnp, jax, u_flat, rank_tab[aid].ravel())
            pick = pref_rep[aid, r_star]
            wi, mi = pick // m_max, pick % m_max
            lw = wi - off
            mine = (lw >= 0) & (lw < w_local)
            lwc = jnp.clip(lw, 0, w_local - 1)
            swp = _owner_bcast(jnp, jax, mine, swap_eff[lwc, mi])
            ltp = _owner_bcast(jnp, jax, mine, lat_tab[g, lwc, mi])
            start = t[wi]
            comp = start + swp + ltp
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

    worker_axis = {
        "w_valid": P("shard"), "lat_tab": P(None, "shard"),
        "sswap": P(None, "shard"), "rank_tab": P(None, "shard"),
    }
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(), P(), P(), P(), worker_axis["w_valid"], P(), P(), P(), P(),
            P(), worker_axis["lat_tab"], worker_axis["sswap"], P(), P(), P(),
            P(), worker_axis["rank_tab"],
        ),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    prog = jax.jit(mapped)
    _PROGRAMS[key] = prog
    return prog


def _sharded_mw_spec_program(res_mode, num_shards, chunk):
    """Chunked sharded Eq. 15: ``pipeline._spec_select_mw``'s speculate-
    K/validate/fallback rounds with the (K, worker, batch, model) tiles
    split along the worker axis.  Per-round picks use the vectorized
    max/min all-reduce argmax; the pool-carry reconstruction chain and
    the accept/commit step run replicated (same ops on every shard from
    owner-broadcast picked scalars) — identical rounds, conflicts and
    decisions to the single-device chunked driver."""
    key = ("shard_mw_spec", res_mode, num_shards, chunk)
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh = shard_mesh(num_shards)

    def fn(n_total_a, t0, res0, wsizes, cap, w_valid, tabs):
        n_total = n_total_a
        w_local = tabs["sswap"].shape[1]
        m_max = tabs["gid"].shape[1]
        n_pad = tabs["gid"].shape[0]
        off = jax.lax.axis_index("shard").astype(jnp.int64) * w_local
        kk = jnp.arange(chunk)

        def decide(sl, tb_l, res_rep_l):
            # (K, Wl, M) local effective swaps/completions, (K, Wl, B, M)
            # tiles, the scalar-order member mean, then the all-reduce
            # first-max pick per chunk row.
            swap_eff = jnp.where(res_rep_l, 0.0, sl["sswap"])
            comp = (tb_l + swap_eff) + sl["lat"]
            gam = _penalty_jnp(
                sl["pen"][:, None, None, None],
                sl["dl"][:, None, :, None],
                comp[:, :, None, :],
            )
            tile = sl["acc"][:, None, :, :] * (1.0 - jnp.clip(gam, 0.0, 1.0))
            u_mean = _chunk_member_mean(
                tile, sl["mask"][:, None, :], sl["bsize"][:, None]
            )
            u_flat = jnp.where(
                sl["valid"][:, None, :] & w_valid[None, :, None], u_mean, -jnp.inf
            ).reshape(chunk, -1)
            r_star = _pick_allreduce(
                jnp, jax, u_flat, sl["rank"].reshape(chunk, -1)
            )
            picks = jnp.take_along_axis(sl["pref"], r_star[:, None], axis=1)[:, 0]
            return picks, swap_eff

        def bcast_at(mine, lw, mi, arr):
            # arr (K, Wl, M): the owner's [k, lw_k, mi_k] scalar per row.
            lwc = jnp.clip(lw, 0, w_local - 1)
            return _owner_bcast(jnp, jax, mine, arr[kk, lwc, mi])

        def body(carry):
            p, t, res, owsel, osel, ostart, olat, rounds, conflicts = carry
            sl = {
                k: jax.lax.dynamic_slice_in_dim(v, p, chunk, axis=0)
                for k, v in tabs.items()
            }

            # 1. Speculate under the frozen boundary pool state.
            t_l = jax.lax.dynamic_slice_in_dim(t, off, w_local)
            res_lb = jax.lax.dynamic_slice_in_dim(res, off, w_local)
            if res_mode == "slot1":
                rep0 = res_lb[None, :, None] == sl["gid"][:, None, :]
            else:
                rep0 = (
                    res_lb[None, :, None, :] == sl["gid"][:, None, :, None]
                ).any(-1)
            pick_s, swap_eff0 = decide(sl, t_l[None, :, None], rep0)
            wi_s, mi_s = pick_s // m_max, pick_s % m_max
            gid_s = jnp.take_along_axis(sl["gid"], mi_s[:, None], axis=1)[:, 0]
            lw_s = wi_s - off
            mine_s = (lw_s >= 0) & (lw_s < w_local)
            sw_s = bcast_at(mine_s, lw_s, mi_s, swap_eff0)
            lt_s = bcast_at(mine_s, lw_s, mi_s, sl["lat"])

            # 2. Reconstruct the implied pool states — replicated chain,
            # byte-for-byte the single-device driver's rstep.
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
            ts_l = jax.lax.dynamic_slice_in_dim(t_states, off, w_local, axis=1)
            rs_l = jax.lax.dynamic_slice_in_dim(res_states, off, w_local, axis=1)
            if res_mode == "slot1":
                rep = rs_l[:, :, None] == sl["gid"][:, None, :]
            else:
                rep = (rs_l[:, :, :, None] == sl["gid"][:, None, None, :]).any(-2)
            pick_t, swap_eff = decide(sl, ts_l[:, :, None], rep)
            wi_t, mi_t = pick_t // m_max, pick_t % m_max
            gid_t = jnp.take_along_axis(sl["gid"], mi_t[:, None], axis=1)[:, 0]
            lw_t = wi_t - off
            mine_t = (lw_t >= 0) & (lw_t < w_local)
            sw_t = bcast_at(mine_t, lw_t, mi_t, swap_eff)
            lt_t = bcast_at(mine_t, lw_t, mi_t, sl["lat"])
            start_t = t_states[kk, wi_t]
            comp_fin = (start_t + sw_t) + lt_t

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
            olat = jax.lax.dynamic_update_slice_in_dim(
                olat, comp_fin - start_t, p, 0
            )

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
        return owsel, osel, ostart, olat, jnp.stack([rounds, conflicts])

    tab_specs = {
        "acc": P(), "mask": P(), "dl": P(), "bsize": P(),
        "lat": P(None, "shard"), "sswap": P(None, "shard"),
        "gid": P(), "valid": P(), "pen": P(), "pref": P(),
        "rank": P(None, "shard"),
    }
    mapped = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P("shard"), tab_specs),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    prog = jax.jit(mapped)
    _PROGRAMS[key] = prog
    return prog


# --------------------------------------------------------------------------
# Replicated Eq. 9/12 + ordering program (per-request policies)
# --------------------------------------------------------------------------


def _acc_order_program(key, ordering, selection, data_aware, app_static):
    """The Eq. 9/12 + ordering head of ``pipeline._per_request_program``
    as a standalone replicated program: sharpened accuracies at the
    REFERENCE gemm shape (sharding the contraction would re-associate
    its rounding — see the module docstring), Eq. 12 priorities, the
    window ordering lexsort, and MaxAcc's carry-independent whole-window
    argmax.  Its outputs feed the sharded selection tables."""
    prog = _PROGRAMS.get(key)
    if prog is not None:
        return prog
    import jax
    import jax.numpy as jnp

    def program(deadlines, arrivals, rids, app_id, valid_tab, per_app):
        n_total = deadlines.shape[0]
        m_max = valid_tab.shape[1]
        prio = jnp.zeros(n_total, dtype=jnp.float64)
        acc = jnp.zeros((n_total, m_max), dtype=jnp.float64)
        for (m_a, has_theta), (theta, trows, idx, d_rel, recalls, prof, sc, pref) in zip(
            app_static, per_app
        ):
            n_a = idx.shape[0]
            a_mat = jnp.tile(prof, (n_a, 1))
            if data_aware and has_theta:
                sharpened = theta @ recalls.T  # Eq. 9, reference shape
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
            sel_all = jnp.argmax(jnp.where(valid_tab[app_id], acc, -jnp.inf), axis=1)
        else:
            sel_all = jnp.zeros(n_total, dtype=jnp.int64)
        return acc, order, sel_all

    prog = jax.jit(program)
    _PROGRAMS[key] = prog
    return prog


# --------------------------------------------------------------------------
# ShardedWindowPipeline
# --------------------------------------------------------------------------


class ShardedWindowPipeline(WindowPipeline):
    """``WindowPipeline`` with the batched tile phases split across a
    device mesh (see the module docstring for the bit-identity layout).
    ``shard=True`` uses every local device; ``shard=N`` uses N.  One
    shard (or the numpy backend) delegates every schedule verbatim to
    the base class — same compiled programs, same cache keys."""

    def __init__(self, apps, sneakpeeks=None, policy=None, backend=None,
                 workers=None, chunk=None, shard=True):
        super().__init__(apps, sneakpeeks=sneakpeeks, policy=policy,
                         backend=backend, workers=workers, chunk=chunk)
        self.shard = shard
        self._shards: int | None = None
        # Stats of the LAST sharded schedule (None when delegated):
        # num_shards, rounds, conflicts (single-carry paths record the
        # speculation rounds; the sequential Eq. 15 scan reports rounds =
        # group count, conflicts = 0).
        self.last_shard_stats: dict | None = None

    def num_shards(self) -> int:
        """Resolved shard count (1 when jax or devices are absent)."""
        if self._shards is None:
            if self.resolved_backend() != "jax":
                self._shards = 1
            else:
                self._shards = resolve_num_shards(self.shard)
        return self._shards

    def schedule(self, requests, now, **kw):
        self.last_shard_stats = None
        return super().schedule(requests, now, **kw)

    def _record_shard_stats(self, rounds, conflicts):
        self.last_shard_stats = {
            "num_shards": self.num_shards(),
            "rounds": int(rounds),
            "conflicts": int(conflicts),
        }

    # -- per-request policies (request-axis sharding) ----------------------
    def _schedule_per_request_jax(self, policy, requests, now, state, arrays):
        shards = self.num_shards()
        if shards <= 1:
            return super()._schedule_per_request_jax(
                policy, requests, now, state, arrays
            )
        from repro.core.types import Schedule, ScheduleEntry

        if policy.selection not in ("locally_optimal", "max_accuracy"):
            raise ValueError(f"unknown selection {policy.selection!r}")
        if policy.ordering not in ("fcfs", "edf", "priority"):
            raise ValueError(f"unknown ordering {policy.ordering!r}")
        wa = arrays if arrays is not None else WindowArrays(requests, self.apps, now)
        tab = self._window_tables(wa)
        app_names = tab["app_names"]
        n_total = len(wa.requests)

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
        fixed = policy.selection == "max_accuracy"
        head_key = (
            "shard_accorder", policy.ordering, policy.selection,
            bool(policy.data_aware), tuple(app_static),
        )
        head = _acc_order_program(
            head_key, policy.ordering, policy.selection,
            bool(policy.data_aware), tuple(app_static),
        )
        with self._enable_x64():
            acc_d, order_d, sel_d = head(
                wa.deadlines, wa.arrivals, np.asarray(wa.rids, dtype=np.int64),
                app_id, jt["valid"], per_app,
            )
            acc_np = np.asarray(acc_d)
            order = np.asarray(order_d)
            sel_all = np.asarray(sel_d)

            # Ordered, padded decision tables — the single-device chunked
            # driver's layout, rows padded to the shard count (inert:
            # valid=False -> -inf utilities).
            aid_o = app_id[order]
            n_pad = pad_rows(n_total, shards)
            pad = n_pad - n_total

            def padr(x, cv=0):
                return np.pad(
                    x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=cv
                )

            tabs = {
                "acc": padr(acc_np[order]),
                "dl": padr(wa.deadlines[order], 1.0),
                "pen": padr(tab["pen"][aid_o]),
                "swap": padr(tab["swap"][aid_o]),
                "lat": padr(tab["lat1"][aid_o]),
                "gid": padr(tab["gid"][aid_o], -2),
                "valid": padr(tab["valid"][aid_o]),
            }
            if fixed:
                tabs["sel"] = padr(sel_all[order])
            mesh = shard_mesh(shards)
            specs = row_specs(mesh, {k: v.shape for k, v in tabs.items()})
            tabs = _place(mesh, tabs, specs)

            prog = _sharded_select_program("per_request", res_mode, shards, fixed)
            k_eff = np.int64(chunk if chunk else n_total)
            sel, starts, lats, stats = prog(
                np.int64(n_total), k_eff, t0, res0, sizes0, cap, tabs
            )
        rounds, conflicts = np.asarray(stats, dtype=np.int64).tolist()
        self._record_shard_stats(rounds, conflicts)
        if chunk:
            self._record_chunk_stats(chunk, n_total, stats)

        local = tab["pref"][aid_o, np.asarray(sel)[:n_total]]
        order_l = order.tolist()
        local_l = local.tolist()
        starts_l = np.asarray(starts)[:n_total].tolist()
        lats_l = np.asarray(lats)[:n_total].tolist()
        requests = wa.requests
        app_of = wa.app_of
        names = {name: wa.app_arrays[name].names for name in wa.req_idx}
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

    # -- grouped policies (group-axis sharding) ----------------------------
    def _schedule_grouped_jax(self, policy, requests, now, state, arrays):
        shards = self.num_shards()
        if shards <= 1:
            return super()._schedule_grouped_jax(policy, requests, now, state, arrays)
        setup = self._grouped_setup(policy, requests, now, state, arrays)
        if setup.get("sched") is not None:  # brute-force branch (<= tau)
            return setup["sched"]
        n_groups = setup["acc"].shape[0]
        t0, res0, gsizes, cap, res_mode = setup["seed"]
        chunk = self._chunk_of(policy)
        with self._enable_x64():
            n_pad = pad_rows(n_groups, shards)
            pad = n_pad - n_groups

            def padr(x, cv=0):
                return np.pad(
                    x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=cv
                )

            tabs = {
                "acc": padr(setup["acc"]),
                "mask": padr(setup["member_mask"]),
                "dl": padr(setup["deadlines"], 1.0),
                "size": padr(setup["sizes"], 1.0),
                "pen": padr(setup["pen_tab"]),
                "swap": padr(setup["swap_tab"]),
                "lat": padr(setup["lat_tab"]),
                "gid": padr(setup["gid_tab"], -2),
                "valid": padr(setup["valid_tab"]),
            }
            mesh = shard_mesh(shards)
            specs = row_specs(mesh, {k: v.shape for k, v in tabs.items()})
            tabs = _place(mesh, tabs, specs)
            prog = _sharded_select_program("grouped", res_mode, shards, False)
            k_eff = np.int64(chunk if chunk else n_groups)
            sel, starts, lats, stats = prog(
                np.int64(n_groups), k_eff, t0, res0, gsizes, cap, tabs
            )
        rounds, conflicts = np.asarray(stats, dtype=np.int64).tolist()
        self._record_shard_stats(rounds, conflicts)
        if chunk:
            self._record_chunk_stats(chunk, n_groups, stats)
        return self._grouped_emit(
            setup, np.asarray(sel)[:n_groups],
            np.asarray(starts)[:n_groups], np.asarray(lats)[:n_groups],
        )

    # -- multi-worker placement (worker-axis sharding) ---------------------
    def _schedule_multiworker_jax(self, policy, requests, now, workers, state,
                                  arrays, lat_scale=None):
        shards = self.num_shards()
        if shards <= 1:
            return super()._schedule_multiworker_jax(
                policy, requests, now, workers, state, arrays, lat_scale
            )
        setup = self._mw_setup(policy, requests, now, workers, state, arrays,
                               lat_scale)
        pool, tab = setup["pool"], setup["tab"]
        m_max = tab["m_max"]
        n_groups = setup["acc"].shape[0]
        n_w = len(workers)
        w_pad = pad_rows(n_w, shards)
        wp = w_pad - n_w

        res_mode = pool.res_mode(state)
        res0 = pool.res[:, 0].copy() if res_mode == "slot1" else pool.res
        # Padded (inert) workers: never valid, never resident, rank +inf.
        t0 = np.pad(pool.t, (0, wp))
        res0 = np.pad(res0, [(0, wp)] + [(0, 0)] * (res0.ndim - 1),
                      constant_values=-1)
        wsizes = np.pad(pool.sizes, [(0, wp), (0, 0)], constant_values=1.0)
        w_valid = np.zeros(w_pad, dtype=bool)
        w_valid[:n_w] = True
        lat_tab = np.pad(setup["lat_tab"], [(0, 0), (0, wp), (0, 0)])
        sswap = np.pad(tab["sswap"], [(0, 0), (0, wp), (0, 0)])
        # rank[a, w, m] = position of (w, m) in the app's tie-break
        # preference permutation (the all-reduce min key); pref_rep maps
        # the winning rank back to the base (w * m_max + m) pick.
        pref = tab["pref"]  # (A, n_w * m_max)
        n_apps = pref.shape[0]
        rank = np.full((n_apps, w_pad, m_max), _RANK_INF, dtype=np.int64)
        inv = np.empty_like(pref)
        ar = np.arange(pref.shape[1], dtype=np.int64)
        for ai in range(n_apps):
            inv[ai, pref[ai]] = ar
        rank[:, :n_w, :] = inv.reshape(n_apps, n_w, m_max)

        chunk = self._chunk_of(policy)
        with self._enable_x64():
            if chunk:
                n_pad = n_groups + chunk

                def padr(x, cv=0):
                    return np.pad(
                        x, [(0, chunk)] + [(0, 0)] * (x.ndim - 1),
                        constant_values=cv,
                    )

                app_id = setup["app_id"]
                tabs = {
                    "acc": padr(setup["acc"]),
                    "mask": padr(setup["member_mask"]),
                    "dl": padr(setup["deadlines"], 1.0),
                    "bsize": padr(setup["bsizes"], 1.0),
                    "lat": padr(lat_tab),
                    "sswap": padr(sswap[app_id]),
                    "gid": padr(tab["gid"][app_id], -2),
                    "valid": padr(tab["valid"][app_id]),
                    "pen": padr(tab["pen"][app_id]),
                    "pref": padr(pref[app_id]),
                    "rank": padr(rank[app_id], _RANK_INF),
                }
                mesh = shard_mesh(shards)
                specs = row_specs(
                    mesh, {k: v.shape for k, v in tabs.items()},
                    axis={"lat": 1, "sswap": 1, "rank": 1, "acc": None,
                          "mask": None, "dl": None, "bsize": None, "gid": None,
                          "valid": None, "pen": None, "pref": None},
                )
                # Replicated tables: no "req" axis -> empty specs.
                from jax.sharding import PartitionSpec as P

                for k in ("acc", "mask", "dl", "bsize", "gid", "valid", "pen",
                          "pref"):
                    specs[k] = P()
                tabs = _place(mesh, tabs, specs)
                prog = _sharded_mw_spec_program(res_mode, shards, chunk)
                out = prog(np.int64(n_groups), t0, res0, wsizes,
                           np.float64(pool.capacity), w_valid, tabs)
                wsel, sel, starts, lats, stats = out
                self._record_chunk_stats(chunk, n_groups, stats)
                rounds, conflicts = np.asarray(stats, dtype=np.int64).tolist()
                self._record_shard_stats(rounds, conflicts)
            else:
                prog = _sharded_mw_program(res_mode, shards)
                wsel, sel, starts, lats = prog(
                    t0, res0, wsizes, np.float64(pool.capacity), w_valid,
                    setup["acc"], setup["member_mask"], setup["deadlines"],
                    setup["bsizes"], setup["app_id"], lat_tab, sswap,
                    tab["gid"], tab["valid"], tab["pen"], pref, rank,
                )
                self._record_shard_stats(n_groups, 0)
        return self._mw_emit(
            setup, workers, np.asarray(wsel)[:n_groups],
            np.asarray(sel)[:n_groups], np.asarray(starts)[:n_groups],
            np.asarray(lats)[:n_groups],
        )
