"""FLOP and byte counts from shapes, against hand-computed values for one layer."""
import dataclasses

import pytest

from bench import flops
from bench.archs import mamba2, transformer
from bench.harness import ROOT
from bench.models import arch, load_config
from bench.tests.tiny import GELU, SSD, TRANSFORMER


def _one(d):
    return dataclasses.replace(d, layers=1)


def test_transformer_layer_by_hand():
    d = _one(TRANSFORMER)  # d=64, H=4, Hkv=2, Dh=16, ff=128, swiglu, V=512
    linear = 64 * 4 * 16 + 2 * 64 * 2 * 16 + 4 * 16 * 64 + 3 * 64 * 128  # 36864
    assert linear == 36_864
    ctx = 5
    assert transformer.token_flops(d, ctx, logits=False) == 2 * 36_864 + 4 * 4 * 16 * ctx
    assert (transformer.token_flops(d, ctx, logits=True)
            == 2 * 36_864 + 4 * 4 * 16 * ctx + 2 * 64 * 512)
    params = 512 * 64 + 64 + (2 * 64 + 36_864)
    assert transformer.param_count(d) == params
    # decode step, batch 2, context 5: weights once + K/V read (5 positions) and written
    kv = 2 * 5 * 2 * 16 * 2 * 2 + 2 * 2 * 16 * 2 * 2
    assert flops.decode_step_cost(d, 2, 5) == (2 * transformer.token_flops(d, 5, True),
                                               params * 2 + kv)


def test_gelu_mlp_has_two_matrices():
    d = _one(GELU)  # MHA: Hkv = 4
    linear = 64 * 4 * 16 * 4 + 2 * 64 * 128
    assert transformer.token_flops(d, 1, logits=False) == 2 * linear + 4 * 4 * 16


def test_ssd_layer_by_hand():
    d = _one(SSD)  # d=64, din=128, H=8, N=16, g=1, W=4
    in_proj = 64 * (2 * 128 + 2 * 16 + 8)
    out_proj = 128 * 64
    d_xbc = 128 + 32
    per = 2 * (in_proj + out_proj) + 2 * 4 * d_xbc + 4 * 128 * 16
    assert mamba2.token_flops(d, 99, logits=False) == per  # no dependence on context
    params = 512 * 64 + 64 + (64 + in_proj + 4 * d_xbc + d_xbc + 3 * 8 + 128 + out_proj)
    assert mamba2.param_count(d) == params
    state = 2 * (3 * 8 * 16 * 16 * 4 + 3 * 3 * d_xbc * 2)
    assert mamba2.state_bytes(d, 3, 10) == state


def test_forward_flops_counts_prompt_and_decode():
    d = _one(TRANSFORMER)
    want = 3 * (sum(transformer.token_flops(d, i + 1, i == 7) for i in range(8))
                + sum(transformer.token_flops(d, 8 + j + 1, True) for j in range(3)))
    assert flops.forward_flops(d, 3, 8, 4) == want


@pytest.mark.parametrize("name", ["sneakpeek-granite8b", "sneakpeek-musicgen"])
def test_param_count_matches_program(name):
    from repro.models import LM

    for dims in load_config(ROOT / "bench" / "configs" / f"{name}.json")["roles"].values():
        assert arch(dims).param_count(dims) == LM(arch(dims).model_config(dims)).num_params()


def test_least_time_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 50.0, peaks) == (10.0, "compute")
    assert flops.least_time(100.0, 50.0, peaks) == (5.0, "memory")
