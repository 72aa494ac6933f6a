"""Per-architecture smoke tests (deliverable f): every assigned arch, as a
reduced same-family config, runs one forward + one train step on CPU with
finite outputs and correct shapes; decode-capable archs also check
prefill+decode consistency against the full forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, cell_supported, get_config
from repro.launch.steps import make_train_step
from repro.models import LM
from repro.training.optimizer import OptimizerConfig, init_opt_state

ALL_ARCHS = sorted(ARCHS.keys())


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


def _tokens(rng, cfg, b=2, s=32):
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch, rng):
    cfg = ARCHS[arch].reduced()
    model = LM(cfg)
    params = model.init(0)
    tokens = _tokens(rng, cfg)
    logits, aux = jax.jit(model.forward)(params, tokens)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_reduces_loss_direction(arch, rng):
    """One optimizer step must run, produce finite metrics, update params."""
    cfg = ARCHS[arch].reduced()
    model = LM(cfg)
    params = model.init(0)
    opt_cfg = OptimizerConfig(learning_rate=1e-3, warmup_steps=1)
    opt = init_opt_state(params, opt_cfg)
    step = jax.jit(make_train_step(model, opt_cfg))
    batch = {"tokens": _tokens(rng, cfg, 2, 33)}
    new_params, new_opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    delta = jax.tree.reduce(
        lambda a, x: a + float(jnp.abs(x[0] - x[1]).sum()),
        jax.tree.map(lambda a, b: (a, b), params, new_params),
        0.0,
    )
    assert delta > 0.0
    assert int(new_opt["step"]) == 1


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_forward(arch, rng):
    cfg = ARCHS[arch].reduced()
    model = LM(cfg)
    params = model.init(0)
    B, S, P = 2, 32, 24
    tokens = _tokens(rng, cfg, B, S)
    full_logits, _ = model.forward(params, tokens)
    logits, cache = model.prefill(params, tokens[:, :P], max_len=S)
    errs = [float(jnp.abs(logits - full_logits[:, P - 1]).max())]
    for t in range(P, S):
        logits, cache = model.decode_step(params, cache, tokens[:, t : t + 1])
        errs.append(float(jnp.abs(logits - full_logits[:, t]).max()))
    assert max(errs) < 2e-3, f"{arch}: prefill/decode diverges from forward ({max(errs):.2e})"


def test_all_assigned_archs_are_registered():
    assigned = [
        "musicgen-medium", "tinyllama-1.1b", "gemma-7b", "gemma3-4b", "granite-8b",
        "llama4-scout-17b-a16e", "llama4-maverick-400b-a17b", "recurrentgemma-9b",
        "mamba2-130m", "chameleon-34b",
    ]
    for name in assigned:
        cfg = get_config(name)
        assert cfg.num_layers > 0


def test_param_counts_match_public_figures():
    expect = {
        "tinyllama-1.1b": (1.0e9, 1.2e9),
        "gemma-7b": (8.0e9, 9.0e9),
        "gemma3-4b": (3.5e9, 4.5e9),
        "granite-8b": (7.5e9, 8.5e9),
        "llama4-scout-17b-16e": (1.0e11, 1.15e11),
        "llama4-maverick-400b-128e": (3.9e11, 4.1e11),
        "recurrentgemma-9b": (8.0e9, 9.5e9),
        "mamba2-130m": (1.2e8, 1.5e8),
        "chameleon-34b": (3.3e10, 3.6e10),
        "granite-4.0-h-small": (3.2e10, 3.25e10),  # published: 32.2 B, about 9 B active
    }
    for name, (lo, hi) in expect.items():
        n = ARCHS[name].param_count()
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B outside [{lo/1e9}, {hi/1e9}]"
    # active params for the MoEs ~ 17B
    for name in ("llama4-scout-17b-16e", "llama4-maverick-400b-128e"):
        a = ARCHS[name].active_param_count()
        assert 1.5e10 <= a <= 1.9e10
    assert 8.5e9 <= ARCHS["granite-4.0-h-small"].active_param_count() <= 9.5e9


def test_cell_support_matrix():
    """44 cells total; long_500k only for sub-quadratic-capable archs."""
    cells = [(a, s) for a in ARCHS for s in SHAPES]
    assert len(cells) == 44
    supported = [c for c in cells if cell_supported(*c)[0]]
    assert len(supported) == 36
    for arch in ("mamba2-130m", "recurrentgemma-9b", "gemma3-4b"):
        assert cell_supported(arch, "long_500k")[0]
    assert not cell_supported("chameleon-34b", "long_500k")[0]


def test_int8_kv_cache_decode_close(rng):
    """kv_quant=True: prefill+decode stays within quantization noise of the
    full forward (the gemma-7b decode_32k HBM hillclimb, EXPERIMENTS §Perf)."""
    import dataclasses

    cfg = dataclasses.replace(ARCHS["tinyllama-1.1b"].reduced(), kv_quant=True)
    model = LM(cfg)
    params = model.init(0)
    B, S, P = 2, 32, 24
    tokens = _tokens(rng, cfg, B, S)
    full_logits, _ = model.forward(params, tokens)
    logits, cache = model.prefill(params, tokens[:, :P], max_len=S)
    errs = [float(jnp.abs(logits - full_logits[:, P - 1]).max())]
    for t_ in range(P, S):
        logits, cache = model.decode_step(params, cache, tokens[:, t_ : t_ + 1])
        errs.append(float(jnp.abs(logits - full_logits[:, t_]).max()))
    assert max(errs) < 0.1  # int8 noise, not drift
    leaves = jax.tree.leaves(cache)
    assert any(getattr(l, "dtype", None) == jnp.int8 for l in leaves)
