"""The yardstick is checked before it judges: ``reference.py`` against
``LlamaForCausalLM`` at a tiny size, both in float32.

Tolerance 1e-5 on logits of magnitude about 0.7 and on a loss near 5.5: the
two compute the same mathematics in float32 under "highest" matmul
precision and differ only in the order of additions (blocked attention and
loss here, a scan over layers there), which moves the last one or two bits
(measured 2.4e-7). A dropped term, a wrong rotary layout or a wrong GQA
head mapping moves the third digit."""
import jax
import numpy as np
import pytest
import reference

TOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(use_recompute=False))
    ids = np.random.RandomState(0).randint(0, 256, (3, 70)).astype(np.int32)
    return paddle, model, ids


def test_forward_logits_agree(tiny):
    paddle, model, ids = tiny
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model(paddle.to_tensor(ids)).value)
    at = np.tile(np.arange(ids.shape[1])[None], (ids.shape[0], 1))
    got = np.asarray(reference.logits_at(
        reference.weights_of(model), reference.hyper_of(model.config),
        ids, at))
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= TOL


def test_loss_agrees(tiny):
    paddle, model, ids = tiny
    with jax.default_matmul_precision("highest"):
        want = float(model(paddle.to_tensor(ids),
                           paddle.to_tensor(ids)).value)
    got = float(reference.loss(reference.weights_of(model),
                               reference.hyper_of(model.config), ids))
    assert abs(got - want) <= TOL


def test_reference_is_causal(tiny):
    _, model, ids = tiny
    w, h = reference.weights_of(model), reference.hyper_of(model.config)
    at = np.tile(np.arange(30)[None], (ids.shape[0], 1))
    a = np.asarray(reference.logits_at(w, h, ids, at))
    changed = ids.copy()
    changed[:, 40:] = (changed[:, 40:] + 1) % 256
    b = np.asarray(reference.logits_at(w, h, changed, at))
    assert np.array_equal(a, b)
