"""The yardstick of the GLM-5.2 cell is checked before it judges:
``reference_glm_moe_dsa.py`` against ``GlmMoeDsaForCausalLM`` at a tiny size,
both in float32; its selection against a token-by-token loop in numpy (the
``shared`` layers' sets ARE the ``full`` layer's); its router (bias in the
selection, not in the weights; the sum over all picks) by hand; and the module
imports nothing from ``paddle_tpu``.

Tolerance 1e-5 on logits of magnitude about 0.5: the same mathematics in
float32 under "highest" matmul precision, another order of additions."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import reference_glm_moe_dsa as reference

from conftest import BENCH

TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaForCausalLM,
                                               glm_moe_dsa_tiny)
    paddle.seed(0)
    model = GlmMoeDsaForCausalLM(glm_moe_dsa_tiny())
    ids = np.random.RandomState(0).randint(0, 256, (2, 40)).astype(np.int32)
    return model, ids


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_glm_moe_dsa.py")) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert sorted(set(names)) == ["functools", "jax", "jax.numpy"]


def test_forward_logits_agree(tiny):
    model, ids = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.forward(ids).value)
    at = np.tile(np.arange(ids.shape[1])[None], (ids.shape[0], 1))
    got = np.asarray(reference.logits_at(
        reference.weights_of(model), reference.hyper_of(model.config),
        ids, at))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL


def test_selection_by_hand(tiny):
    """Layer 0's set of every query, from the module docstring's formula in
    numpy float64, token by token; the layers that borrow hold the same."""
    model, ids = tiny
    w, hy = reference.weights_of(model), reference.hyper_of(model.config)
    _, sets = reference.hidden_states(w, hy, ids[:1], with_sets=True)
    sets = np.asarray(sets)[:, 0]
    assert (sets[1] == sets[0]).all() and (sets[2] == sets[0]).all()
    assert (sets[4] == sets[3]).all()
    d = {n: np.asarray(a, np.float64)[0] for n, a in w["dense"].items()}
    x = np.asarray(w["embed"], np.float64)[ids[0]]

    def rms(v, g):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + hy["eps"]) * g

    def rope(v):                        # [S, heads, D], half-split pairs
        n = v.shape[-1]
        inv = hy["theta"] ** (-np.arange(0, n, 2) / n)
        ang = np.arange(v.shape[0])[:, None] * inv[None]
        s, c = np.sin(ang)[:, None], np.cos(ang)[:, None]
        a, b = v[..., :n // 2], v[..., n // 2:]
        return np.concatenate([a * c - b * s, b * c + a * s], -1)

    h = rms(x, d["input_ln"])
    c_q = rms(h @ d["wq_a"], d["q_a_ln"])
    heads, dim, r = hy["index_heads"], hy["index_dim"], hy["rope"]
    q = (c_q @ d["idx_wq_b"]).reshape(-1, heads, dim)
    q = np.concatenate([rope(q[..., :r]), q[..., r:]], -1)
    k = h @ d["idx_wk"]
    k = k - k.mean(-1, keepdims=True)
    k = k / np.sqrt((k * k).mean(-1, keepdims=True) + hy["index_eps"]) \
        * d["idx_k_ln_w"] + d["idx_k_ln_b"]
    k = np.concatenate([rope(k[:, None, :r])[:, 0], k[:, r:]], -1)
    wt = (h @ d["idx_w"]) * heads ** -0.5 * dim ** -0.5
    for t in range(ids.shape[1]):
        score = (wt[t][:, None] * np.maximum(q[t] @ k[:t + 1].T, 0)).sum(0)
        want = np.sort(np.argsort(-score, kind="stable")[:hy["index_topk"]])
        assert (np.flatnonzero(sets[0, t]) == want).all(), t


def test_router_by_hand():
    """Sigmoid scores; the bias decides the picks and is in no weight; the
    weights sum to the scale over ALL picks."""
    scores = jnp.asarray([[0.9, 0.5, 0.49, 0.1], [0.3, 0.31, 0.8, 0.2]])
    bias = jnp.asarray([0.0, -0.02, 0.02, 0.0])
    hy = dict(top_k=2, norm_topk_prob=True, routed_scale=2.5)
    e, w = reference.route(scores, bias, jnp.full((2, 2), -1), hy)
    assert np.asarray(e).tolist() == [[0, 2], [2, 0]]   # 0.51 > 0.48; .30 ..
    np.testing.assert_allclose(
        np.asarray(w), [[2.5 * .9 / 1.39, 2.5 * .49 / 1.39],
                        [2.5 * .8 / 1.1, 2.5 * .3 / 1.1]], rtol=1e-6)
    # teacher-forced: the told experts at this router's own scores
    e, w = reference.route(scores, bias, jnp.asarray([[1, 3], [-1, -1]]), hy)
    assert np.asarray(e).tolist() == [[1, 3], [2, 0]]
    np.testing.assert_allclose(np.asarray(w)[0],
                               [2.5 * .5 / .6, 2.5 * .1 / .6], rtol=1e-6)


def test_router_scores_are_the_selections_own(tiny):
    model, ids = tiny
    w, hy = reference.weights_of(model), reference.hyper_of(model.config)
    at = np.tile(np.arange(ids.shape[1])[None], (ids.shape[0], 1))
    _, scores = reference.logits_at(w, hy, ids, at, with_router=True)
    scores = np.asarray(scores)                     # [L_expert, B, S, E]
    assert scores.shape == (4, 2, 40, 8)
    with jax.default_matmul_precision("highest"):
        _, picks = model.forward(ids, return_router_picks=True)
    ref_sets = np.sort(np.argsort(scores, -1)[..., -hy["top_k"]:], -1)
    assert (np.sort(np.asarray(picks), -1) == ref_sets).all()
