"""granite-4.0-h-small as a plug-in of the benchmark: its layout is the
program's, its counts are checked by hand, its readers read what the
program's spans give, and a sound run at a tiny size is correct."""
import json

import jax
import pytest

from bench import flops, harness
from bench.archs import granitemoehybrid as g4
from bench.models import arch, load_config
from bench.tests import tiny
from bench.weights import make_weights

CONFIG = harness.ROOT / "bench" / "configs" / "sneakpeek-granite4h.json"
GROUP = json.loads(CONFIG.read_text())
# The published layer types and multipliers at tiny widths: 10 layers (one
# period, attention at 5), 2 of 8 experts held from the third, top 3.
TINY = dict(GROUP, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, intermediate_size=32,
            shared_intermediate_size=48, vocab_size=512, num_local_experts=2,
            num_experts_per_tok=3, deployment=dict(GROUP["deployment"], router_experts=8,
                                                   expert_offset=2))
SEED = 2**31 + 11


def tiny_config() -> dict:
    """The configuration file's contents with both models cut to tiny sizes."""
    cfg = load_config(CONFIG)
    cfg["roles"] = {"fast": tiny.SSD, "accurate": g4.dims(TINY)}
    return cfg


def test_layout_is_the_programs():
    from repro.models import LM

    d = g4.dims(TINY)
    assert d.period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4 and d.layers == 10
    lm = LM(g4.model_config(d))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shapes(make_weights(d, SEED, 0)) == shapes(lm.abstract_params())
    assert g4.param_count(d) == lm.num_params()


def test_the_cut_is_the_published_stage_and_share():
    d = load_config(CONFIG)["roles"]["accurate"]
    assert arch(d) is g4
    assert (d.layers, d.experts, d.held, d.offset, d.top_k) == (10, 72, 9, 0, 10)
    assert (d.d, d.ff, d.shared_ff, d.vocab, d.head_dim) == (4096, 768, 1536, 100352, 128)
    assert g4.param_count(d) == 2_414_692_992
    cfg = g4.model_config(d)
    assert cfg.pattern == ("ssd:moe",) * 5 + ("attn:moe",) + ("ssd:moe",) * 4
    assert (cfg.n_periods, cfg.n_tail, cfg.held_experts) == (1, 0, 9)


def test_costs_by_hand():
    """One decode step of 16 rows at context 9, and a forward of 12 rows of
    8 prompt + 4 new tokens, of the cut at its published widths."""
    d = load_config(CONFIG)["roles"]["accurate"]
    mamba = 4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096  # in_proj, out_proj
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024  # q, o; k, v of 8 heads
    expert = 3 * 4096 * 768
    moe = 4096 * 72 + 3 * 4096 * 1536 + 10 * 9 / 72 * expert  # router, shared, 1.25 held pairs
    conv_state = 2 * 4 * (8192 + 256) + 4 * 8192 * 128

    def tok(context, logits):
        return (9 * (2 * (mamba + moe) + conv_state) + 2 * (attn + moe)
                + 4 * 32 * 128 * context + (2 * 4096 * 100352 if logits else 0))

    reached = 9 * (1 - (1 - 10 / 72) ** 16)
    assert reached == pytest.approx(8.1774, abs=1e-4)
    weights = 2 * (2_414_692_992 - 10 * 9 * expert + 10 * reached * expert)
    state = 9 * 2 * (16 * 128 * 64 * 128 * 4 + 16 * 3 * 8448 * 2) + 16 * 10 * 8 * 128 * 2 * 2
    ops, nbytes = flops.decode_step_cost(d, 16, 9)
    assert ops == pytest.approx(16 * tok(9, True), rel=1e-12)
    assert ops == pytest.approx(54_464_643_072, rel=1e-12)
    assert nbytes == pytest.approx(weights + state, rel=1e-12)
    assert nbytes == pytest.approx(5.897338169e9, rel=1e-9)
    fwd = 12 * (sum(tok(i + 1, i == 7) for i in range(8)) + sum(tok(9 + j, True) for j in range(3)))
    assert flops.forward_flops(d, 12, 8, 4) == pytest.approx(fwd, rel=1e-12)
    assert fwd == pytest.approx(380_271_796_224, rel=1e-12)


def _rec(spans=None, forwards=()):
    d = load_config(CONFIG)["roles"]
    return {"roles": d, "dims_by_model": {x.name: x for x in d.values()}, "prompt_len": 8,
            "new_tokens": 4, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "spans": spans, "forwards": list(forwards)}


def test_readers_by_hand():
    from bench.metrics import routed_decode_roofline, routed_mfu

    rec = _rec()
    acc, fast = rec["roles"]["accurate"], rec["roles"]["fast"]
    rec["spans"] = {"forwards": [
        {"model": acc.name, "rows": 12, "padded": 16, "decode_dev_s": 0.03},
        {"model": acc.name, "rows": 12, "padded": 16, "decode_dev_s": 0.03},
        {"model": fast.name, "rows": 1, "padded": 1, "decode_dev_s": 0.001},
    ]}
    least = sum(max(o / 197e12, b / 819e9) for o, b in
                (flops.decode_step_cost(acc, 16, 9 + j) for j in range(3)))
    assert routed_decode_roofline.read(rec) == pytest.approx(100 * least / 0.03)
    rec["forwards"] = [
        {"model": acc.name, "rows": 12, "prefill_s": 0.01, "decode_s": 0.03},
        {"model": fast.name, "rows": 2, "prefill_s": 0.002, "decode_s": 0.004},
    ]
    assert routed_mfu.read(rec) == pytest.approx(
        100 * flops.forward_flops(acc, 12, 8, 4) / (0.04 * 197e12))


def test_readers_find_nothing_without_the_accurate_models_forwards():
    from bench.metrics import routed_decode_roofline, routed_mfu

    assert routed_decode_roofline.read(_rec()) is None
    assert routed_mfu.read(_rec()) is None
    fast = load_config(CONFIG)["roles"]["fast"].name
    rec = _rec({"forwards": [{"model": fast, "rows": 1, "padded": 1, "decode_dev_s": 0.001}]},
               [{"model": fast, "rows": 1, "prefill_s": 0.01, "decode_s": 0.01}])
    assert routed_decode_roofline.read(rec) is None and routed_mfu.read(rec) is None


def test_sound_run_is_correct():
    res = harness.run("granite4h.paper", SEED, 0.3, False, require_tpu=False,
                      config_override=tiny_config(), traffic_override=tiny.traffic())
    assert res["correct"], res["checks"]
    assert res["checks"]["gap_accurate"]["limit"] == g4.GAP_LIMIT
    assert res["failed"] == 0 and res["attempted"] == 3 * 2 * 3
    assert set(res["metrics"]) == {"attain", "utility", "p50_ms", "setup_s"}
