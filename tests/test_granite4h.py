"""granite-4.0-h-small's mechanisms on the CPU, in float32 at a small size: the
preset's ``reduced()`` with 2 of its 8 experts held.  Prefill and cached
decode through the hybrid cache (KV beside SSM state) against the plain
reference's full forward; the chip's share of an expert layer; no token
dropped at the served shapes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.archs import granitemoehybrid as g4
from bench.weights import make_weights
from repro.configs import get_config
from repro.models import LM, moe

# Both sides compute the same equations in float32 at highest precision;
# they differ only in the order of their sums (chunked SSD scan against a
# step-by-step recurrence, a cache against the whole sequence), which moves
# logits by ~1e-6 of their spread.  A departure from the model's equations
# moves them by far more than 1e-4 of it.
TOL = 1e-4


def _cfg(**kw):
    cfg = get_config("granite-4.0-h-small").reduced()
    return dataclasses.replace(cfg, **{"experts_here": 2, "expert_offset": 4, **kw})


def _dims(cfg) -> g4.Dims:
    """The reference's spec of a program config."""
    period = tuple("attention" if k.startswith("attn") else "mamba" for k in cfg.pattern)
    return g4.Dims(
        name=cfg.name, layers=cfg.num_layers, d=cfg.d_model, vocab=cfg.vocab_size,
        kinds=tuple(period[i % cfg.period] for i in range(cfg.num_layers)), period=period,
        heads=cfg.num_heads, kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        attn_scale=cfg.attention_multiplier, rope=False, ssd_heads=cfg.ssd_heads,
        headdim=cfg.ssd_headdim, d_state=cfg.ssd_state, ngroups=cfg.ssd_ngroups,
        d_conv=cfg.conv_width, experts=cfg.num_experts, held=cfg.held_experts,
        offset=cfg.expert_offset, top_k=cfg.moe_top_k, ff=cfg.moe_d_ff,
        shared_ff=cfg.shared_width, embed_mult=cfg.embedding_multiplier,
        residual_mult=cfg.residual_multiplier, logits_div=cfg.logits_scaling, eps=cfg.norm_eps)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def test_reduced_preset_is_a_share_of_the_published_layer():
    pub = get_config("granite-4.0-h-small")
    cfg = _cfg()
    assert (pub.num_experts, pub.held_experts, pub.moe_top_k) == (72, 72, 10)
    assert (cfg.num_experts, cfg.held_experts, cfg.expert_offset) == (8, 2, 4)
    assert cfg.pattern == ("ssd:moe",) * 5 + ("attn:moe",) + ("ssd:moe",) * 4 == pub.pattern
    assert cfg.shared_width != cfg.moe_d_ff and pub.shared_width == 1536
    assert (cfg.position_embedding, cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == ("none", 12.0, 0.0078125, 0.22, 16.0)


def test_prefill_and_decode_through_the_hybrid_cache_match_the_reference():
    cfg = _cfg()
    d = _dims(cfg)
    w = make_weights(d, 2**31 + 17, 0)
    lm = LM(cfg)
    shapes = lambda t: jax.tree.map(lambda a: a.shape, t)  # noqa: E731
    assert shapes(lm.abstract_params()) == shapes(w)  # the shared expert at its own width
    p = _f32(w)
    toks = jnp.asarray(np.random.default_rng(3).integers(0, d.vocab, (3, 12)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = lm.forward(p, toks)
        lg, cache = lm.prefill(p, toks[:, :8], max_len=12)
        steps = [lg]
        for j in range(3):
            lg, cache = lm.decode_step(p, cache, toks[:, 8 + j:9 + j])
            steps.append(lg)
    kinds = {k for c in cache["blocks"] for k in c}
    assert {"k", "v", "conv", "state"} <= kinds  # KV beside SSM state in one cache
    ref = g4.logits(d, w, toks)
    scale = float(ref.std())
    assert float(jnp.max(jnp.abs(full - ref))) < TOL * scale
    assert float(jnp.max(jnp.abs(jnp.stack(steps, 1) - ref[:, 7:11]))) < TOL * scale


def _layer_params(seed, d_model, experts, held, ff, shared_ff):
    ks = jax.random.split(jax.random.key(seed), 7)
    n = jax.random.normal
    return {
        "router": n(ks[0], (d_model, experts)) * d_model ** -0.5,
        "w_gate": n(ks[1], (held, d_model, ff)) * d_model ** -0.5,
        "w_up": n(ks[2], (held, d_model, ff)) * d_model ** -0.5,
        "w_down": n(ks[3], (held, ff, d_model)) * ff ** -0.5,
        "shared": {"w_gate": n(ks[4], (d_model, shared_ff)) * d_model ** -0.5,
                   "w_up": n(ks[5], (d_model, shared_ff)) * d_model ** -0.5,
                   "w_down": n(ks[6], (shared_ff, d_model)) * shared_ff ** -0.5},
    }


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips of 2 experts each: their results, with the shared expert
    (which each computes alike) counted once, are the reference's layer
    with all 8 experts."""
    uncut = _cfg(experts_here=0, expert_offset=0)
    d = _dims(uncut)
    m = _layer_params(5, d.d, 8, 8, d.ff, d.shared_ff)
    h = jax.random.normal(jax.random.key(6), (2, 8, d.d))
    with jax.default_matmul_precision("highest"):
        shared = g4._swiglu(False, h, m["shared"]["w_gate"], m["shared"]["w_up"],
                            m["shared"]["w_down"])
        parts = []
        for i in range(4):
            share = dataclasses.replace(uncut, experts_here=2, expert_offset=2 * i)
            mine = dict(m, **{k: m[k][2 * i:2 * i + 2] for k in ("w_gate", "w_up", "w_down")})
            parts.append(moe.held_forward(mine, h, share)[0])
        whole = g4._moe(d, False, h, m)
    total = sum(parts) - 3 * shared
    assert float(jnp.max(jnp.abs(total - whole))) < TOL * float(whole.std())
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 0.1 * float(whole.std())


@pytest.mark.parametrize("tokens", [16, 128], ids=["decode", "prefill"])
def test_no_token_is_dropped_at_the_served_shapes(tokens):
    """Every (token, held expert) pair the router picks is computed, whatever
    the capacity settings: the result is the token-by-token sum, and the
    counters count each pair and each expert reached."""
    cfg = _cfg()
    m = _layer_params(7, cfg.d_model, 8, 2, cfg.moe_d_ff, cfg.shared_width)
    x = jax.random.normal(jax.random.key(8), (tokens // 8, 8, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, counts = moe.held_forward(m, x, cfg)
        y_cap, _ = moe.held_forward(m, x, dataclasses.replace(cfg, capacity_factor=1e-3,
                                                               moe_group=1))
        t = np.asarray(x.reshape(-1, cfg.d_model))
        logits = t @ np.asarray(m["router"])
        want = np.zeros_like(t)
        pairs, reached = 0, set()
        for i in range(len(t)):
            top = np.argsort(-logits[i], kind="stable")[:cfg.moe_top_k]
            g = np.exp(logits[i, top] - logits[i, top].max())
            g /= g.sum()
            for e, ge in zip(top, g):
                if 4 <= e < 6:
                    pairs += 1
                    reached.add(int(e))
                    j = e - 4
                    hid = jax.nn.silu(t[i] @ m["w_gate"][j]) * (t[i] @ m["w_up"][j])
                    want[i] += ge * np.asarray(hid @ m["w_down"][j])
            s = m["shared"]
            want[i] += np.asarray((jax.nn.silu(t[i] @ s["w_gate"]) * (t[i] @ s["w_up"]))
                                  @ s["w_down"])
    got = np.asarray(y).reshape(t.shape)
    assert np.max(np.abs(got - want)) < TOL * want.std()
    assert np.array_equal(np.asarray(y), np.asarray(y_cap))
    assert pairs > 0 and [int(c) for c in counts] == [pairs, len(reached)]


def test_counters_sum_over_layers_and_only_held_models_count():
    cfg = _cfg()
    lm = LM(cfg)
    p = lm.init(0)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, cfg.vocab_size, (4, 8)), jnp.int32)
    lg, cache, n = lm.prefill(p, toks, max_len=10, counts=True)
    lg2, _ = lm.prefill(p, toks, max_len=10)
    assert np.array_equal(np.asarray(lg), np.asarray(lg2))
    layers = cfg.num_layers
    assert 0 < int(n[0]) <= 32 * cfg.moe_top_k * layers and 0 < int(n[1]) <= 2 * layers
    _, _, n1 = lm.decode_step(p, cache, toks[:, :1], counts=True)
    assert 0 < int(n1[1]) <= 2 * layers and int(n1[0]) >= int(n1[1])
    plain = LM(get_config("mamba2-130m").reduced())
    q = plain.init(0)
    assert plain.prefill(q, toks % 128, max_len=10, counts=True)[2] is None
