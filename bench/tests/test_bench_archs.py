"""The served model as a plug-in: each architecture module's counts are the
ones the benchmark has always used, and a new architecture is new files only."""
import dataclasses
import importlib
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.archs
from bench import check, flops, reference
from bench.harness import ROOT
from bench.models import arch, load_config
from bench.tests.tiny import SSD_GROUP
from bench.weights import make_weights

# (param_count, decode_step_cost at (4, 9) and (16, 9), forward_flops of 3
# and 12 rows of 8 prompt + 4 new tokens), as the counts were before the
# architectures moved into modules of their own.
MAMBA2_130M = (128_983_488, (1_106_558_976, 411_026_304), (4_426_235_904, 870_204_288),
               7_507_279_872, 30_029_119_488)
PINNED = {
    ("sneakpeek-granite8b", "fast"): MAMBA2_130M,
    ("sneakpeek-granite8b", "accurate"): (
        4_127_346_688, (33_028_177_920, 8_257_642_496), (132_112_711_680, 8_266_489_856),
        263_997_554_688, 1_055_990_218_752),
    ("sneakpeek-musicgen", "fast"): MAMBA2_130M,
    ("sneakpeek-musicgen", "accurate"): (
        1_362_249_216, (10_907_418_624, 2_736_294_912), (43_629_674_496, 2_771_684_352),
        89_824_886_784, 359_299_547_136),
}


@pytest.mark.parametrize("name,role", sorted(PINNED), ids=lambda x: x)
def test_counts_are_pinned(name, role):
    d = load_config(ROOT / "bench" / "configs" / f"{name}.json")["roles"][role]
    got = (arch(d).param_count(d), flops.decode_step_cost(d, 4, 9),
           flops.decode_step_cost(d, 16, 9), flops.forward_flops(d, 3, 8, 4),
           flops.forward_flops(d, 12, 8, 4))
    assert got == PINNED[name, role]


# A transformer whose blocks have no FFN, with a pattern of two block
# positions and a tail (3 layers: one period, then one layer): everything
# it needs is in this file.
TOY = '''"""Attention-only blocks (no FFN), for the extension test."""
import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import HI, mm, rms, rope, scan_layers, tied_logits

GAP_LIMIT = 0.15


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    layers: int
    d: int
    vocab: int
    heads: int
    head_dim: int


def dims(c):
    return Dims(c["model_name"], c["layers"], c["d"], c["vocab"], c["heads"], c["head_dim"])


def model_config(d):
    from repro.configs.base import ModelConfig

    return ModelConfig(name=d.name, family="dense", num_layers=d.layers, d_model=d.d,
                       vocab_size=d.vocab, num_heads=d.heads, num_kv_heads=d.heads,
                       head_dim=d.head_dim, pattern=("attn:none", "attn:none"),
                       tie_embeddings=True)


def _block(d, lead):
    H, Dh = d.heads, d.head_dim
    qkv = (lead + (d.d, H, Dh), "normal", d.d ** -0.5)
    return {"pre_norm": {"scale": (lead + (d.d,), "scale", 0.1)},
            "attn": {"wq": qkv, "wk": qkv, "wv": qkv,
                     "wo": (lead + (H, Dh, d.d), "normal", (H * Dh) ** -0.5)}}


def param_layout(d):
    periods = (d.layers // 2,)
    return {"embed": {"embedding": ((d.vocab, d.d), "normal", 0.02)},
            "blocks": [_block(d, periods), _block(d, periods)],
            "tail": [_block(d, ())] * (d.layers % 2),
            "final_norm": {"scale": ((d.d,), "scale", 0.1)}}


def param_count(d):
    return d.vocab * d.d + d.d + d.layers * (d.d + 4 * d.d * d.heads * d.head_dim)


def token_flops(d, context, logits):
    hd = d.heads * d.head_dim
    return d.layers * (8 * d.d * hd + 4 * hd * context) + (2 * d.d * d.vocab if logits else 0)


def forward_flops(d, rows, prompt_len, new_tokens):
    return flops.tokens_forward(token_flops, d, rows, prompt_len, new_tokens)


def decode_step_cost(d, batch, context):
    kv = d.layers * batch * (context + 1) * 2 * d.heads * d.head_dim * flops.BF16
    return batch * token_flops(d, context, True), param_count(d) * flops.BF16 + kv


def _layer(d, quant, x, p):
    a = p["attn"]
    h = rms(x, p["pre_norm"]["scale"], 1e-6)
    q, k, v = (mm("bsd,dhk->bshk", h, a[n], quant, (-1,), (0,)) for n in ("wq", "wk", "wv"))
    q, k = rope(q, 10000.0), rope(k, 10000.0)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HI) / jnp.sqrt(float(d.head_dim))
    n = x.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v, precision=HI)
    return x + mm("bqhk,hkd->bqd", o, a["wo"], quant, (-2, -1), (0, 1))


@functools.partial(jax.jit, static_argnums=(0, 3))
def logits(d, params, tokens, quant=False):
    def period(x, ps):
        return functools.reduce(lambda x, p: _layer(d, quant, x, p), ps, x)

    def blocks(x):
        x = scan_layers(period, params["blocks"], x)
        for p in params["tail"]:
            x = _layer(d, quant, x, jax.tree.map(lambda a: a.astype(jnp.float32), p))
        return x

    return tied_logits(params, tokens, quant, 1e-6, blocks)
'''


@pytest.fixture
def new_arch(tmp_path, monkeypatch):
    """Where a module written into it is found as ``bench.archs.<name>``."""
    pkg = tmp_path / "archs"
    pkg.mkdir()
    monkeypatch.setattr(bench.archs, "__path__", [*bench.archs.__path__, str(pkg)])
    importlib.invalidate_caches()
    yield pkg
    for name in [m for m in sys.modules if m.startswith("bench.archs.toy")]:
        del sys.modules[name]


def _config(tmp_path, arch_name):
    cfg = {"model_name": "toy", "arch": arch_name, "layers": 3, "d": 64, "vocab": 512,
           "heads": 4, "head_dim": 16, "fast_model": SSD_GROUP}
    path = tmp_path / "toy.json"
    path.write_text(json.dumps(cfg))
    return path


def test_a_new_architecture_is_new_files_only(new_arch, tmp_path):
    from repro.models import LM

    (new_arch / "toy_attn.py").write_text(TOY)
    roles = load_config(_config(tmp_path, "toy_attn"))["roles"]
    d = roles["accurate"]
    mod = arch(d)
    assert mod.__name__ == "bench.archs.toy_attn"
    assert arch(roles["fast"]).__name__ == "bench.archs.mamba2"

    w = make_weights(d, 2**31 + 7, 0)
    lm = LM(mod.model_config(d))
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)  # noqa: E731
    assert shapes(w) == shapes(lm.abstract_params())
    assert len(w["blocks"]) == 2 and len(w["tail"]) == 1
    assert mod.param_count(d) == lm.num_params()
    assert flops.forward_flops(d, 3, 8, 4) == mod.forward_flops(d, 3, 8, 4) > 0
    assert flops.decode_step_cost(d, 4, 9) == mod.decode_step_cost(d, 4, 9)

    # the reference against the program's forward, both in float32
    lm = LM(dataclasses.replace(mod.model_config(d), dtype="float32"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, d.vocab, (3, 11)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = lm.forward(p32, toks)
    ref = reference.logits(d, w, toks)
    assert float(jnp.max(jnp.abs(full - ref))) < 1e-4 * float(ref.std())

    # greedy tokens of the program read within the limit; altered ones do not
    prompts = np.asarray(toks[:, :8])
    seq = prompts
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            nxt = np.asarray(lm.forward(p32, jnp.asarray(seq))[0][:, -1].argmax(-1))
            seq = np.concatenate([seq, nxt[:, None]], 1)
    served = seq[:, 8:].astype(np.int32)
    assert check.served_gap(d, w, prompts, served, 4) <= mod.GAP_LIMIT
    assert check.served_gap(d, w, prompts, (served + 1) % d.vocab, 4) > mod.GAP_LIMIT


@pytest.mark.parametrize("name", ["toy_missing", "no/such"])
def test_unknown_architecture_names_file_and_key(new_arch, tmp_path, name):
    path = _config(tmp_path, name)
    with pytest.raises(ValueError, match="arch") as e:
        load_config(path)
    assert str(path) in str(e.value)
