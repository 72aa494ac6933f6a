"""The plain reference against the program's model code, at tiny sizes on
the CPU in float32: the same weights give the same logits, through the full
forward and through prefill + cached decode.  The float8 control differs."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference
from bench.models import arch
from bench.tests.tiny import GELU, SSD, TRANSFORMER
from bench.weights import make_weights


@pytest.mark.parametrize("dims", [TRANSFORMER, GELU, SSD], ids=lambda d: d.name)
def test_reference_matches_program_in_f32(dims):
    from repro.models import LM

    w = make_weights(dims, 2**31 + 99, 0)
    lm = LM(dataclasses.replace(arch(dims).model_config(dims), dtype="float32"))
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, dims.vocab, (3, 11)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        full, _ = lm.forward(p32, toks)
        lg, cache = lm.prefill(p32, toks[:, :8], max_len=12)
        steps = [lg]
        for j in range(3):
            lg, cache = lm.decode_step(p32, cache, toks[:, 8 + j:9 + j])
            steps.append(lg)
    ref = reference.logits(dims, w, toks)
    scale = float(ref.std())
    assert float(jnp.max(jnp.abs(full - ref))) < 1e-4 * scale
    assert float(jnp.max(jnp.abs(jnp.stack(steps, 1) - ref[:, 7:]))) < 1e-4 * scale
    ctl = reference.logits(dims, w, toks, True)
    assert float(jnp.max(jnp.abs(ctl - ref))) > 1e-2 * scale


def test_weights_are_seeded():
    a = make_weights(SSD, 5, 0)
    b = make_weights(SSD, 5, 0)
    c = make_weights(SSD, 6, 0)
    d = make_weights(SSD, 5, 1)
    leaves = lambda t: [np.asarray(x, np.float32) for x in jax.tree.leaves(t)]  # noqa: E731
    assert all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(b)))
    assert not all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(c)))
    assert not all(np.array_equal(x, y) for x, y in zip(leaves(a), leaves(d)))
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(a))


def test_token_gaps():
    ref = np.array([[[0.0, 1.0, 3.0, 2.0]]])
    gaps = check.token_gaps(ref, np.array([[3]]))
    assert gaps.shape == (1, 1)
    assert gaps[0, 0] == pytest.approx(1.0 / ref.std())
    assert check.token_gaps(ref, np.array([[2]]))[0, 0] == 0.0


def test_knn_reference_and_ties():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(50, 4)).astype(np.float32)
    y = rng.integers(0, 3, 50)
    q = rng.normal(size=(6, 4)).astype(np.float32)
    votes, tie = check.knn_reference(q, x, y, 5, 3)
    assert votes.shape == (6, 3) and (votes.sum(1) == 5).all()
    assert not tie.any()
    assert check.knn_wrong_rows([(q, votes)], x, y, 5, 3) == (6, 0, 0)
    bad = votes.copy()
    bad[0] = np.roll(bad[0], 1) if bad[0].max() < 5 else bad[0][::-1]
    assert check.knn_wrong_rows([(q, bad)], x, y, 5, 3)[1] == int(np.any(bad != votes, 1).sum())
    # a duplicated training row at the k-th / (k+1)-th boundary is a tie
    xt = np.concatenate([x, x[:1]])
    yt = np.concatenate([y, [(y[0] + 1) % 3]])
    _, tie = check.knn_reference(x[:1], xt, yt, 1, 3)
    assert tie[0]
