"""What ``correct`` means in a training cell can fail: the token-by-token
forward check of ``kinds/train.py`` passes the program as it is and fails a
program whose mathematics is wrong, at the cell's own tolerance.

The tiny model's head is scaled so that its logits have the standard
deviation they have at the cell's sizes (0.02 x sqrt(4096) = 1.28; here
0.02 x sqrt(64) x 8), because the tolerance is an absolute number of nats
and what a wrong hidden state moves is proportional to the logits' size.
The program runs in float32 here, so what is shown is the check's power
against wrong mathematics, not the bf16 error it must let pass: that is
measured on the chip (PERF.md section 6)."""
import json
import os

import numpy as np
import pytest
import reference
import trafficgen

from conftest import BENCH
from kinds import train

ROWS, SEQ, TOKENS = 2, 64, 16
with open(os.path.join(BENCH, "traffic", "pretrain-s4096.json")) as f:
    TOLERANCE = json.load(f)["check"]["tolerance_nat"]


@pytest.fixture()
def tiny():
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(use_recompute=True, loss_chunk=16))
    model.lm_head.set_value(model.lm_head.value * 8.0)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, 256, (ROWS, SEQ)).astype(np.int32))
    ref_nll = np.asarray(reference.token_nll(
        reference.weights_of(model), reference.hyper_of(model.config),
        np.asarray(ids.value)))
    return model, ids, ref_nll


def step_of(model, learning_rate=3e-4):
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW
    return TrainStep(model, lambda loss, _lab: loss,
                     AdamW(parameters=model.parameters(),
                           learning_rate=learning_rate))


def worst_delta(model, ids, ref_nll):
    tokens = trafficgen.train_check_tokens(0, ROWS, SEQ, TOKENS)
    return max(abs(d) for d in
               train.forward_check(step_of(model), ids, ref_nll, tokens))


def test_the_program_as_it_is_passes(tiny):
    model, ids, ref_nll = tiny
    assert worst_delta(model, ids, ref_nll) <= 1e-5


def test_a_mask_that_is_not_causal_fails(tiny, monkeypatch):
    from paddle_tpu.models import llama
    model, ids, ref_nll = tiny
    causal = llama._attention
    monkeypatch.setattr(llama, "_attention",
                        lambda q, k, v, causal=True: causal_off(q, k, v))

    def causal_off(q, k, v):
        return causal(q, k, v, causal=False)

    assert worst_delta(model, ids, ref_nll) > 5 * TOLERANCE


def test_a_skipped_layer_fails(tiny):
    model, ids, ref_nll = tiny
    for name in ("wo", "w_down"):       # layer 1 adds nothing to the stream
        p = getattr(model, name)
        p.set_value(p.value.at[1].set(0.0))
    assert worst_delta(model, ids, ref_nll) > 5 * TOLERANCE


def test_the_mean_alone_would_not_have_failed_them(tiny):
    """Why the check is token by token: with random labels the batch mean
    hardly depends on the hidden state (REVIEW of PR 23 measured 0.003 at
    the cell's sizes against a tolerance of 0.02)."""
    model, ids, ref_nll = tiny
    for name in ("wo", "w_down"):
        p = getattr(model, name)
        p.set_value(p.value.at[1].set(0.0))
    got = train.eval_loss(step_of(model), ids, ids)
    per_token = worst_delta(model, ids, ref_nll)
    assert abs(got - float(ref_nll.mean())) < per_token / 4


def test_an_update_that_is_not_applied_fails_the_update_check(tiny):
    model, ids, _ = tiny
    for lr, fell in ((3e-4, True), (0.0, False)):
        step = step_of(model, learning_rate=lr)
        loss0 = float(step.step((ids, ids), (ids,)).value)
        assert (train.eval_loss(step, ids, ids) < loss0) is fell
