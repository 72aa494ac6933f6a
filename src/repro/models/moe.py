"""Routed MoE: two layers, chosen by ``cfg.moe_router``.

``moe_router="topk_softmax"`` (``held_forward``; granite-4.0-h): the router
scores all ``num_experts``, the softmax runs over each token's top-k
logits, and the layer holds a contiguous block of ``held_experts`` of them
(from ``expert_offset``), as one chip of an expert-parallel deployment does.
It computes its own experts' part of the result for every token routed to
them (no capacity, nothing dropped) plus the shared expert, and counts the
(token, held expert) pairs routed and the held experts reached.

``moe_router="gshard"`` (``moe_forward``), the rest of this docstring:
GShard/Switch-style grouped capacity dispatch (top-k, EP-ready).

Tokens are split into groups of ``moe_group``; per (group, expert)
capacity C = ceil(group * top_k / E * capacity_factor).  Dispatch/combine
are one-hot einsums — (G, Tg, E, C) stays small because C shrinks with
the group size — so the SPMD partitioner can turn token<->expert
movement into all-to-alls when experts are sharded over the ``model``
mesh axis.  Overflow tokens are dropped (standard capacity dropping);
the residual connection keeps their representation intact.

Gradient flow follows Switch: the dispatch mask is a constant (argmax);
gradients reach the router through the combine gate probabilities.
Load-balancing aux loss: E * sum_e f_e * p_e  (Switch eq. 4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_act
from repro.models.layers import _act
from repro.models.spec import P

__all__ = ["moe_spec", "moe_forward", "held_forward"]


def moe_spec(d_model: int, num_experts: int, d_ff: int, gated: bool, shared: bool,
             held: int = 0, shared_d_ff: int = 0) -> dict:
    """Router over all ``num_experts``; weights of ``held`` of them (0: all);
    a shared expert of width ``shared_d_ff`` (0: ``d_ff``)."""
    held = held or num_experts
    spec = {
        "router": P((d_model, num_experts), ("embed", "experts"), init="small"),
        "w_up": P((held, d_model, d_ff), ("experts", "embed", "ffn")),
        "w_down": P((held, d_ff, d_model), ("experts", "ffn", "embed")),
    }
    if gated:
        spec["w_gate"] = P((held, d_model, d_ff), ("experts", "embed", "ffn"))
    if shared:
        from repro.models.layers import mlp_spec

        spec["shared"] = mlp_spec(d_model, shared_d_ff or d_ff, gated)
    return spec


def held_forward(params, x, cfg):
    """The held experts' share of a top-k-softmax MoE layer.

    x: (B, S, D) -> (y, counts): ``y`` is the gated sum of the held
    experts' outputs over the tokens routed to them, plus the shared expert
    (every chip of the deployment computes it alike, and it counts once);
    ``counts`` int32 (2,): (token, held expert) pairs routed, and held
    experts reached by at least one token.

    Every held expert runs on every token, and the gates (zero where a
    token is not routed to it) pick the results: at serving shapes (16
    tokens a decode step, 128 a prefill) the step reads the held experts'
    weights once either way, and the dense pass has no data-dependent
    shape.
    """
    from repro.models.layers import mlp

    b, s, d = x.shape
    t = x.reshape(-1, d)
    logits = (t @ params["router"]).astype(jnp.float32)  # (T, E): all experts
    top, idx = jax.lax.top_k(logits, cfg.moe_top_k)  # (T, k)
    gates = jax.nn.softmax(top, axis=-1)
    held = params["w_up"].shape[0]
    mine = (idx - cfg.expert_offset)[..., None] == jnp.arange(held)  # (T, k, held)
    gate = jnp.sum(jnp.where(mine, gates[..., None], 0.0), axis=1)  # (T, held)
    routed = jnp.any(mine, axis=1)
    up = jnp.einsum("td,edf->etf", t, params["w_up"])
    if "w_gate" in params:
        h = _act(cfg.activation, jnp.einsum("td,edf->etf", t, params["w_gate"])) * up
    else:
        h = _act(cfg.activation, up)
    out = jnp.einsum("etf,efd->etd", h, params["w_down"])
    y = jnp.einsum("te,etd->td", gate.astype(out.dtype), out)
    if "shared" in params:
        y = y + mlp(params["shared"], t, cfg.activation)
    counts = jnp.stack([jnp.sum(routed), jnp.sum(jnp.any(routed, axis=0))]).astype(jnp.int32)
    return y.reshape(b, s, d).astype(x.dtype), counts


def _capacity(group: int, top_k: int, num_experts: int, factor: float) -> int:
    c = int(group * top_k * factor / num_experts) + 1
    return max(4, -(-c // 4) * 4)  # round up to a multiple of 4


def moe_forward(params, x, cfg):
    """x: (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    e = cfg.num_experts
    tokens = x.reshape(-1, d)
    t_total = tokens.shape[0]
    group = min(cfg.moe_group, t_total)
    if t_total % group:
        raise ValueError(f"token count {t_total} not divisible by moe_group {group}")
    ng = t_total // group
    xg = tokens.reshape(ng, group, d)
    xg = shard_act(xg, "moe_tokens")

    logits = jnp.einsum("gtd,de->gte", xg, params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (G, Tg, E)

    cap = _capacity(group, cfg.moe_top_k, e, cfg.capacity_factor)

    # Per-round CONSTANT dispatch one-hots + differentiable scalar gates.
    # The gate multiplies OUTSIDE the (G,Tg,E,C) einsums, so no fp32
    # combine tensor exists and the only gradient paths through the big
    # dispatch tensors are the (sharding-annotated) token einsums — this
    # is what keeps the MoE backward memory-sane at 512-way SPMD.
    dispatches, gates = [], []
    remaining = probs
    fill = jnp.zeros((ng, e), jnp.float32)  # slots used per (group, expert)
    for _ in range(cfg.moe_top_k):
        eidx = jnp.argmax(remaining, axis=-1)  # (G, Tg)
        gate = jnp.take_along_axis(remaining, eidx[..., None], axis=-1)[..., 0]
        onehot_e = jax.nn.one_hot(eidx, e, dtype=jnp.float32)  # (G, Tg, E)
        # Position of each token within its expert's capacity buffer.
        pos = jnp.cumsum(onehot_e, axis=1) - 1.0 + fill[:, None, :]  # (G, Tg, E)
        pos_tok = jnp.sum(pos * onehot_e, axis=-1)  # (G, Tg)
        keep = pos_tok < cap
        onehot_c = jax.nn.one_hot(pos_tok.astype(jnp.int32), cap, dtype=jnp.float32)
        d_k = onehot_e[..., None] * onehot_c[:, :, None, :] * keep[..., None, None]
        dispatches.append(jax.lax.stop_gradient(d_k.astype(x.dtype)))
        gates.append((gate * keep).astype(jnp.float32))
        fill = fill + jnp.sum(onehot_e * keep[..., None], axis=1)
        remaining = remaining * (1.0 - onehot_e)  # mask chosen expert for next k

    dispatch_total = dispatches[0]
    for d_k in dispatches[1:]:
        dispatch_total = dispatch_total + d_k
    # Reshard the einsum operands to g-over-data BEFORE the dispatch: the
    # target (E: model, G: data) layout is then one local e-slice away,
    # instead of an (unsupported) joint reshard that makes the SPMD
    # partitioner replicate the full token tensor per device.
    xg_row = shard_act(xg, "moe_tokens_row")
    dispatches = [shard_act(d_k, "moe_dispatch") for d_k in dispatches]
    dispatch_total = shard_act(dispatch_total, "moe_dispatch")
    # (G, Tg, E, C) x (G, Tg, D) -> (E, G, C, D): the EP all-to-all boundary.
    expert_in = jnp.einsum("gtec,gtd->egcd", dispatch_total, xg_row)
    expert_in = shard_act(expert_in, "moe_expert_in")
    up = jnp.einsum("egcd,edf->egcf", expert_in, params["w_up"])
    if "w_gate" in params:
        gate_h = jnp.einsum("egcd,edf->egcf", expert_in, params["w_gate"])
        h = _act(cfg.activation, gate_h) * up
    else:
        h = _act(cfg.activation, up)
    h = shard_act(h, "moe_expert_ffn")
    out_e = jnp.einsum("egcf,efd->egcd", h, params["w_down"])
    out_e = shard_act(out_e, "moe_expert_in")  # same (E, G, C, D) layout
    y = jnp.zeros_like(xg_row)
    for d_k, gate in zip(dispatches, gates):
        routed = jnp.einsum("gtec,egcd->gtd", d_k, out_e)
        y = y + gate[..., None].astype(routed.dtype) * routed
    y = shard_act(y, "moe_tokens")  # back to the residual-stream layout

    # Switch aux loss (per-token mean): E * sum_e f_e * p_e
    f_e = jnp.mean(
        jax.nn.one_hot(jnp.argmax(probs, -1), e, dtype=jnp.float32), axis=(0, 1)
    )
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f_e * p_e)

    if "shared" in params:
        from repro.models.layers import mlp

        y = y + mlp(params["shared"], xg, cfg.activation)

    return y.reshape(b, s, d).astype(x.dtype), aux
