"""Compositional roofline cost model (dry-run companion).

XLA's ``compiled.cost_analysis()`` counts while-loop (lax.scan) bodies
ONCE, so a scanned-layer model under-reports FLOPs/bytes by ~n_periods x
and the attention block loops under-report by ~n_blocks x.  Instead of
unrolling the full model (compile-time explosion at 512-way SPMD), the
roofline is composed from independently compiled pieces, each of which
contains no scan over repeated compute:

  total = stub + n_periods * period + tail

  * stub   — embed -> final_norm -> logits (+ loss & bwd for train):
             the non-layer work, fully counted.
  * period — one full pattern period applied to the residual stream,
             with attention UNROLLED (static block loops, masked, no
             causal skipping — FLOP-identical to the production scan
             path) and, for train, value_and_grad under the same remat
             policy as the real step.
  * tail   — the remainder layers (same machinery, tail kinds).

Collective bytes compose the same way (each piece's census is per
invocation).  Peak memory does NOT compose; it is taken from the full
compile in dryrun.py.  Methodology recorded in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import use_sharding
from repro.launch import shardings as shd
from repro.launch.hlo_analysis import collective_bytes
from repro.models import blocks as blocks_mod
from repro.models.attention import attention_options
from repro.models.layers import logits_from_embed, rmsnorm
from repro.models.spec import abstract_params, logical_axes
from repro.models.transformer import _tail_kinds

__all__ = ["composed_cost"]


def _cost_of(jitted, *args) -> dict:
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
    out = {"flops": 0.0, "bytes": 0.0, "collectives": {"total_bytes": 0}}
    try:
        ca = compiled.cost_analysis()
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes"] = float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        out["error"] = str(e)
    try:
        out["collectives"] = collective_bytes(compiled.as_text())
    except Exception as e:
        out["collectives"] = {"total_bytes": 0, "error": str(e)}
    return out


def _act_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _abstract(tree_spec, dtype):
    return abstract_params(tree_spec, dtype=dtype)


def _unroll_chunks(cfg, seq_len):
    """Chunk sizes for the unrolled-attention period compile: at most
    ~16x16 blocks so the HLO stays small."""
    q = max(cfg.attn_q_chunk, seq_len // 16 or seq_len)
    kv = max(cfg.attn_kv_chunk, seq_len // 16 or seq_len)
    return min(q, seq_len), min(kv, seq_len)


def _period_params_spec(cfg, kinds):
    return [blocks_mod.block_spec(cfg, k) for k in kinds]


def _apply_kinds_full(pp, x, cfg, kinds):
    aux = jnp.zeros((), jnp.float32)
    for p, kind in zip(pp, kinds):
        x, a = blocks_mod.block_full(p, x, cfg, kind)
        aux = aux + a
    return x, aux


def composed_cost(cfg, shape, mesh, policy, opt_cfg=None, skip_masked_blocks: bool = False):
    """Returns {"stub": cost, "period": cost, "tail": cost, "totals": {...}}.

    ``skip_masked_blocks`` switches the unrolled attention to true causal
    block skipping (the §Perf hillclimb variant).
    """
    import dataclasses

    b, s = shape.global_batch, shape.seq_len
    d = cfg.d_model
    dtype = _act_dtype(cfg)
    qc, kvc = _unroll_chunks(cfg, s if shape.step != "decode" else 1)
    cfg_u = dataclasses.replace(cfg, attn_q_chunk=qc, attn_kv_chunk=kvc)

    from repro.distributed.policies import dp_axes as _dpa

    dpx = _dpa(mesh)
    dpx = dpx if len(dpx) > 1 else dpx[0]

    def named(ps_tree):
        return shd.as_named(ps_tree, mesh)

    from repro.distributed.sharding import params_pspecs
    from jax.sharding import NamedSharding, PartitionSpec

    def x_sharding(seq):
        # Mirror the policy's residual-stream rule (act_btd), including the
        # dim-0 batch candidate LIST (widest divisible split wins) — the
        # pieces must see the same tokens/device as the real step.
        rule = policy.act_rules.get("act_btd", (None, None, None))
        spec = [None, None, None]
        dim0 = rule[0] if len(rule) > 0 else None
        candidates = dim0 if isinstance(dim0, list) else [dim0]
        for cand in candidates:
            if cand is None:
                continue
            names = cand if isinstance(cand, tuple) else (cand,)
            size = 1
            for a in names:
                size *= mesh.shape[a]
            if b % size == 0:
                spec[0] = cand
                break
        seq_rule = rule[1] if len(rule) > 1 else None
        seq_rule = seq_rule[0] if isinstance(seq_rule, list) and seq_rule else seq_rule
        if seq_rule == "model" and seq % mesh.shape["model"] == 0:
            spec[1] = "model"
        return NamedSharding(mesh, PartitionSpec(*spec))

    results = {}
    with mesh, use_sharding(mesh, policy), attention_options(
        unroll=True, skip_masked_blocks=skip_masked_blocks
    ):
        # ------------------------------------------------ stub
        from repro.models.layers import embed_spec, embed_tokens
        from repro.models.spec import P as _P

        stub_spec = {
            "embed": embed_spec(cfg.vocab_size, d),
            "final_norm": {"scale": _P((d,), (None,), init="zeros")},
        }
        if not cfg.tie_embeddings:
            stub_spec["lm_head"] = _P((cfg.vocab_size, d), ("vocab", "embed"), init="small")
        stub_axes = logical_axes(stub_spec)
        stub_abs = _abstract(stub_spec, dtype)
        stub_ps = params_pspecs(stub_axes, stub_abs, policy, mesh)

        seq = s if shape.step != "decode" else 1

        def stub_fwd(p, tokens):
            x = embed_tokens(p["embed"], tokens, scale_by_dim=cfg.embed_scale).astype(dtype)
            x = rmsnorm(p["final_norm"], x)
            table = {"embedding": p.get("lm_head", p["embed"]["embedding"])}
            if shape.step == "decode":
                # the real decode_step reads logits from the LAST position
                # only — (B, V), which is what the vocab-sharded "logits"
                # rule (rank 2) applies to.
                return logits_from_embed(table, x[:, -1, :], cfg.logit_softcap)
            return logits_from_embed(table, x, cfg.logit_softcap)

        if shape.step == "train":
            # Chunked xent with a STATIC python loop over chunks (the real
            # loss uses lax.scan, whose body cost_analysis counts once).
            chunk = max(cfg.xent_chunk, s // 8)

            def stub_loss(p, tokens):
                x = embed_tokens(p["embed"], tokens[:, :-1], scale_by_dim=cfg.embed_scale).astype(dtype)
                x = rmsnorm(p["final_norm"], x)
                table = p.get("lm_head", p["embed"]["embedding"])
                tgt = tokens[:, 1:]
                total = jnp.zeros((), jnp.float32)
                n = x.shape[1]
                from repro.distributed.sharding import shard_act as _sa

                for lo in range(0, n, chunk):
                    hi = min(lo + chunk, n)
                    xc = _sa(x[:, lo:hi], "xent_act")
                    logits = (xc @ table.T).astype(jnp.float32)

                    logits = _sa(logits, "logits")
                    if cfg.logit_softcap:
                        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
                    logz = jax.nn.logsumexp(logits, axis=-1)
                    gold = jnp.take_along_axis(logits, tgt[:, lo:hi][..., None], axis=-1)[..., 0]
                    total = total + (logz - gold).sum()
                return total / (tokens.shape[0] * n)

            def stub_step(p, tokens):
                return jax.value_and_grad(stub_loss)(p, tokens)

            tok = jax.ShapeDtypeStruct((b, s + 1), jnp.int32)
        else:
            stub_step = stub_fwd
            tok = jax.ShapeDtypeStruct((b, seq), jnp.int32)
        tok_sh = NamedSharding(mesh, shd.token_pspec(b, mesh, full_mesh=(shape.step == "train")))
        results["stub"] = _cost_of(
            jax.jit(stub_step, in_shardings=(named(stub_ps), tok_sh)), stub_abs, tok
        )

        # ------------------------------------------------ period / tail
        def piece_cost(kinds):
            pp_spec = _period_params_spec(cfg_u, kinds)
            pp_axes = logical_axes(pp_spec)
            pp_abs = _abstract(pp_spec, dtype)
            pp_ps = params_pspecs(pp_axes, pp_abs, policy, mesh)
            x_abs = jax.ShapeDtypeStruct((b, seq, d), dtype)
            xs = x_sharding(seq)

            if shape.step == "train":
                def piece_loss(pp, x):
                    def body(pp_inner, x_inner):
                        y, aux = _apply_kinds_full(pp_inner, x_inner, cfg_u, kinds)
                        return y, aux

                    body_ck = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
                    y, aux = body_ck(pp, x)
                    return jnp.sum(y.astype(jnp.float32) ** 2) * 1e-6 + aux

                def piece_step(pp, x):
                    return jax.value_and_grad(piece_loss)(pp, x)
            elif shape.step == "prefill":
                def piece_step(pp, x):
                    caches = []
                    for p, kind in zip(pp, kinds):
                        x, cache, _ = blocks_mod.block_prefill(p, x, cfg_u, kind, s)
                        caches.append(cache)
                    return x, caches
            else:  # decode
                def piece_step(pp, x, caches, pos):
                    new = []
                    for p, cache, kind in zip(pp, caches, kinds):
                        x, c, _ = blocks_mod.block_decode(p, x, cache, pos, cfg_u, kind)
                        new.append(c)
                    return x, new

            if shape.step == "decode":
                cache_abs = []
                for kind in kinds:
                    tpl = blocks_mod.cache_spec(cfg_u, kind, b, s)
                    cache_abs.append(
                        {n: jax.ShapeDtypeStruct(shp, dt) for n, (shp, dt) in tpl.items()}
                    )
                cache_ps = shd.cache_pspecs(cache_abs, mesh)
                pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
                return _cost_of(
                    jax.jit(
                        piece_step,
                        in_shardings=(named(pp_ps), xs, named(cache_ps), None),
                        donate_argnums=(2,),
                    ),
                    pp_abs, x_abs, cache_abs, pos_abs,
                )
            return _cost_of(
                jax.jit(piece_step, in_shardings=(named(pp_ps), xs)), pp_abs, x_abs
            )

        results["period"] = piece_cost(list(cfg.pattern)) if cfg.n_periods > 0 else None
        tail_kinds = _tail_kinds(cfg)
        results["tail"] = piece_cost(tail_kinds) if tail_kinds else None

    # ------------------------------------------------ compose
    def total(key):
        t = results["stub"].get(key, 0.0) or 0.0
        if results["period"]:
            t += cfg.n_periods * (results["period"].get(key, 0.0) or 0.0)
        if results["tail"]:
            t += results["tail"].get(key, 0.0) or 0.0
        return t

    def total_coll():
        t = results["stub"]["collectives"].get("total_bytes", 0)
        if results["period"]:
            t += cfg.n_periods * results["period"]["collectives"].get("total_bytes", 0)
        if results["tail"]:
            t += results["tail"]["collectives"].get("total_bytes", 0)
        return t

    results["totals"] = {
        "flops": total("flops"),
        "bytes": total("bytes"),
        "collective_bytes": total_coll(),
    }
    return results
