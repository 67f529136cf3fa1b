"""The yardstick of the DeepSeek-V2 cell is checked before it judges:
``reference_deepseek_v2.py`` against ``DeepseekV2ForCausalLM`` at a tiny size,
both in float32; its group-limited routing and its held share against a
token-by-token loop in numpy; its YaRN numbers by hand; and the module
imports nothing from ``paddle_tpu``.

Tolerance 1e-5 on logits of magnitude about 0.6: the two compute the same
mathematics in float32 under "highest" matmul precision and differ in the
order of additions (head groups and a masked loop over held experts here;
padded whole-width attention and grouped matmuls there), measured 2e-7."""
import ast
import math
import os

import jax
import numpy as np
import pytest
import reference_deepseek_v2 as reference

from conftest import BENCH

TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                               deepseek_v2_tiny)
    paddle.seed(0)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny())
    ids = np.random.RandomState(0).randint(0, 256, (3, 50)).astype(np.int32)
    return model, ids


def test_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "reference_deepseek_v2.py")) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert sorted(set(names)) == ["functools", "jax", "jax.numpy", "math"]


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


def test_router_scores_and_the_models_picks(tiny):
    model, ids = tiny
    w, h = reference.weights_of(model), reference.hyper_of(model.config)
    at = np.tile(np.arange(0, 50, 7)[None], (ids.shape[0], 1))
    logits, scores = reference.logits_at(w, h, ids, at, with_router=True)
    c = model.config
    n_expert_layers = c.num_hidden_layers - c.first_k_dense_replace
    assert scores.shape == (n_expert_layers, 3, at.shape[1],
                            c.router_experts)
    scores = np.asarray(scores)
    # the judged scores: the kept group of four as it is, the other group's
    # below the rule's 2nd score, so the top 2 share a group
    top = np.argsort(scores, -1)[..., -2:]
    assert (top[..., 0] // 4 == top[..., 1] // 4).all()
    # the kept group's scores are the softmax's own: they sum to under 1
    kept = np.take_along_axis(scores, np.sort(top // 4 * 4, -1)[..., :1]
                              + np.arange(4), -1)
    assert (scores > 0).all() and (kept.sum(-1) < 1).all()
    assert np.array_equal(np.asarray(logits),
                          np.asarray(reference.logits_at(w, h, ids, at)))
    with jax.default_matmul_precision("highest"):
        _, picks = model.forward(ids, return_router_picks=True)
    picks = np.take_along_axis(np.asarray(picks), at[None, :, :, None], 2)
    want = np.argsort(scores, -1)[..., -c.num_experts_per_tok:]
    assert np.array_equal(np.sort(picks, -1), np.sort(want, -1))


def _swiglu_np(g, wg, wu, wd):
    a = g @ wg
    return (a / (1 + np.exp(-a)) * (g @ wu)) @ wd


@pytest.mark.parametrize("first_held,n_held", [(0, 12), (0, 4), (8, 4)])
def test_group_limited_routing_and_held_share_by_hand(first_held, n_held):
    """12 experts in 3 groups of 4, the best 2 groups kept, 3 a token; the
    held range adds only its own experts' terms."""
    rng = np.random.default_rng(first_held + n_held)
    tokens, hid, wid, n_exp = 9, 16, 8, 12
    g = rng.standard_normal((tokens, hid)).astype(np.float32)
    logits = rng.standard_normal((tokens, n_exp)).astype(np.float32) * 2
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    w = {n: rng.standard_normal(s).astype(np.float32) * 0.3
         for n, s in (("w_gate", (n_exp, hid, wid)),
                      ("w_up", (n_exp, hid, wid)),
                      ("w_down", (n_exp, wid, hid)))}
    hy = dict(top_k=3, norm_topk_prob=False, n_group=3, topk_group=2,
              first_held=first_held, routed_scale=2.5)
    masked = np.asarray(reference.group_limited_scores(probs, 3, 2))
    want = np.zeros_like(g)
    for t in range(tokens):
        best = probs[t].reshape(3, 4).max(-1)
        kept = np.argsort(best)[-2:]
        mine = np.where(np.isin(np.arange(n_exp) // 4, kept), probs[t], 0.0)
        np.testing.assert_allclose(masked[t], mine, rtol=1e-6)
        for e in np.argsort(mine)[-3:]:
            if first_held <= e < first_held + n_held:
                want[t] += 2.5 * mine[e] * _swiglu_np(
                    g[t], w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    held = {n: jax.numpy.asarray(a[first_held:first_held + n_held])
            for n, a in w.items()}
    own = np.full((tokens, 3), -1, np.int32)        # the rule's own picks
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._routed(
            jax.numpy.asarray(g), jax.numpy.asarray(probs), own, held, hy))
        # told the rule's picks, the same; told others, those at their raw
        # scores, whatever group they lie in
        rule = np.argsort(masked, -1)[:, -3:].astype(np.int32)
        same = np.asarray(reference._routed(
            jax.numpy.asarray(g), jax.numpy.asarray(probs), rule, held, hy))
        other = np.tile(np.asarray([[1, 5, 9]], np.int32), (tokens, 1))
        told = np.asarray(reference._routed(
            jax.numpy.asarray(g), jax.numpy.asarray(probs), other, held, hy))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(same, want, atol=2e-5, rtol=1e-4)
    forced = np.zeros_like(g)
    for t in range(tokens):
        for e in (1, 5, 9):
            if first_held <= e < first_held + n_held:
                forced[t] += 2.5 * probs[t, e] * _swiglu_np(
                    g[t], w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    np.testing.assert_allclose(told, forced, atol=2e-5, rtol=1e-4)


def test_judged_scores_by_hand():
    """Two groups of three, one kept, two a token. The rule's world: group
    0's scores, its 2nd 0.25. The other world: group 1 in its place, 0.3 /
    0.4 of the way, its 2nd score 0.02: an expert there scores 0.25 x 0.75
    x min(1, s / 0.02)."""
    raw = jax.numpy.asarray([[0.4, 0.25, 0.02, 0.3, 0.02, 0.01]])
    got = np.asarray(reference.judged_scores(raw, 2, 1, 2))[0]
    np.testing.assert_allclose(
        got, [0.4, 0.25, 0.02, 0.1875, 0.1875, 0.09375], rtol=1e-6)
    masked = np.asarray(reference.group_limited_scores(raw, 2, 1))[0]
    np.testing.assert_allclose(masked, [0.4, 0.25, 0.02, 0, 0, 0])
    assert set(np.argsort(got)[-2:]) == set(np.argsort(masked)[-2:])
    # groups that differ by 1 %: the other group's two best are 1 % short,
    # its third is short by its own share of that world's 2nd score too
    near = jax.numpy.asarray([[0.4, 0.25, 0.02, 0.396, 0.3, 0.03]])
    r = np.asarray(reference.judged_scores(near, 2, 1, 2))[0]
    kth = np.sort(r)[-2]
    assert kth == np.float32(0.25)
    np.testing.assert_allclose((kth - r[3:]) / kth,
                               [0.01, 0.01, 1 - 0.99 * 0.1], rtol=1e-5)
    # every group kept: the softmax as it is
    np.testing.assert_allclose(
        np.asarray(reference.judged_scores(raw, 2, 2, 2))[0], raw[0])


def test_yarn_and_softmax_scale_by_hand():
    yarn = (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    f = reference.yarn_frequencies(64, 1e4, yarn)
    assert f[0] == 1.0 and f[10] == 1e4 ** (-10 / 32)      # untouched
    assert abs(f[31] - 1e4 ** (-31 / 32) / 40) < 1e-12     # over the factor
    assert 1e4 ** (-16 / 32) / 40 < f[16] < 1e4 ** (-16 / 32)  # blended
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(reference.mscale(40, 0.707) - m) < 1e-12
    hyper = {"nope": 128, "rope": 64, "yarn": yarn}
    assert abs(reference.softmax_scale(hyper) - 192 ** -0.5 * m * m) < 1e-12
    assert reference.softmax_scale({**hyper, "yarn": None}) == 192 ** -0.5
