"""The yardstick of the OLMoE cell is checked before it judges:
``reference_olmoe.py`` against ``OlmoeForCausalLM`` at a tiny size, both in
float32, and its mixture of experts against a token-by-token loop in numpy.

Tolerance 1e-5 on logits of magnitude about 0.6: the two compute the same
mathematics in float32 under "highest" matmul precision and differ in the
order of additions (every expert over every token with a mask here; pairs
ordered by expert and grouped matmuls there), measured 2e-7. A wrong expert,
a missing QK-norm or a renormalised weight moves the second digit
(``tests/test_olmoe_serving.py`` shows each)."""
import jax
import numpy as np
import pytest
import reference_olmoe as reference

TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.olmoe import OlmoeForCausalLM, olmoe_tiny
    paddle.seed(0)
    model = OlmoeForCausalLM(olmoe_tiny())
    ids = np.random.RandomState(0).randint(0, 256, (3, 50)).astype(np.int32)
    return model, ids


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


def test_router_probabilities_and_the_models_picks(tiny):
    model, ids = tiny
    w, h = reference.weights_of(model), reference.hyper_of(model.config)
    at = np.tile(np.arange(0, 50, 7)[None], (ids.shape[0], 1))
    logits, probs = reference.logits_at(w, h, ids, at, with_router=True)
    c = model.config
    assert probs.shape == (c.num_hidden_layers, 3, at.shape[1],
                           c.num_experts)
    assert np.abs(np.asarray(probs).sum(-1) - 1).max() <= 1e-5
    assert np.array_equal(np.asarray(logits),
                          np.asarray(reference.logits_at(w, h, ids, at)))
    # in float32 the model picks the reference's top-k, as a set
    with jax.default_matmul_precision("highest"):
        _, picks = model.forward(ids, return_router_picks=True)
    picks = np.take_along_axis(np.asarray(picks), at[None, :, :, None], 2)
    want = np.argsort(np.asarray(probs), -1)[..., -c.num_experts_per_tok:]
    assert np.array_equal(np.sort(picks, -1), np.sort(want, -1))


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_experts_equal_a_token_by_token_loop(norm_topk_prob):
    rng = np.random.RandomState(1)
    s, hid, width, n_exp, k = 9, 16, 8, 6, 2
    hn = rng.randn(s, hid).astype(np.float32)
    probs = np.asarray(jax.nn.softmax(rng.randn(s, n_exp), -1), np.float32)
    w = {"w_gate": rng.randn(n_exp, hid, width).astype(np.float32) * .3,
         "w_up": rng.randn(n_exp, hid, width).astype(np.float32) * .3,
         "w_down": rng.randn(n_exp, width, hid).astype(np.float32) * .3}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(reference._experts(hn, probs, w, k, norm_topk_prob))
    want = np.zeros_like(hn)
    for t in range(s):
        top = np.argsort(probs[t])[-k:]
        scale = probs[t, top].sum() if norm_topk_prob else 1.0
        for e in top:
            g = hn[t] @ w["w_gate"][e]
            y = (g / (1 + np.exp(-g)) * (hn[t] @ w["w_up"][e])) \
                @ w["w_down"][e]
            want[t] += probs[t, e] / scale * y
    assert np.abs(got - want).max() <= 1e-5


def test_reference_is_causal(tiny):
    model, ids = tiny
    w, h = reference.weights_of(model), reference.hyper_of(model.config)
    at = np.tile(np.arange(20)[None], (ids.shape[0], 1))
    a = np.asarray(reference.logits_at(w, h, ids, at))
    changed = ids.copy()
    changed[:, 30:] = (changed[:, 30:] + 1) % 256
    b = np.asarray(reference.logits_at(w, h, changed, at))
    assert np.array_equal(a, b)


def test_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        src = f.read()
    assert "paddle_tpu" not in src.split('"""', 2)[2]
