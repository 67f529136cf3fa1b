"""OLMoE through the serving engine (ISSUE 26, tests (b), (c), (d)).

(b) The engine against the plain reference ON LOGITS: every token the
engine generates is produced from logits that equal the float32 reference's
full forward at that position, for a whole-prompt prefill then decoding
through the paged cache, for a prompt longer than ``prefill_chunk`` (chunks
through the unified step) and for a sequence preempted and recomputed.
Tolerance 1e-4 of the largest logit: float32 on both sides (conftest sets
matmul precision ``highest``), so what is left is summation order, about
1e-6; a wrong expert, a missing QK-norm, a renormalised weight or a dropped
token moves a logit by more than 1e-2, and ``test_wrong_variant_fails``
shows each of the four failing.
(c) Every engine switch whose program was not taught the layer raises.
(d) Compile-once: steps with different routing share one program.
"""
import os
import sys

import jax
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models.olmoe import OlmoeForCausalLM
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_olmoe  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=96, decode_chunk=1,
                prefill_chunk=32)


def _model(attention="jnp"):
    return serving_support.model("olmoe", seed=7, decode_attention=attention)


def _engine(model, **kw):
    """The shared helper at this file's geometry (3 slots, chunks of 32, the
    engine's own block)."""
    return serving_support.engine_as_given(model, **{**GEOMETRY, **kw})


#: what the recording programs write to: one list for the process, because
#: the programs traced with the recorder inside are kept on their model
#: (``_recorded_programs``) for the recording tests that follow
_RECORDS = []
_REAL_HEAD_LOGITS = decode_mod._head_logits


def _recording(last_h, head):
    logits = _REAL_HEAD_LOGITS(last_h, head)
    jax.debug.callback(lambda x: _RECORDS.append(np.asarray(x)), logits,
                       ordered=True)
    return logits


def _serve_recording_logits(model, prompt, n_new, monkeypatch,
                            preempt_after=None, fresh=False):
    """Run one request through an engine whose programs record, and return
    (tokens, the logits row each token was sampled from). Every program
    computes its logits in ``decode._head_logits``: a whole-prompt prefill
    for the group's rows, a unified step for every slot. The recording
    programs are the model's, traced once; ``fresh`` for a test that patched
    a layer in, which must be traced and which nobody else may run."""
    records = _RECORDS
    del records[:]
    monkeypatch.setattr(decode_mod, "_head_logits", _recording)
    eng = serving_support.watch_prefill_programs(_engine(
        model, jit_cache={} if fresh else model.__dict__.setdefault(
            "_recorded_programs", {})))
    rows = []

    def on_token(seq, _tok):
        # a record a program, in dispatch order: a whole-prompt prefill's
        # has the group's rows, a unified step's one row a slot. Token 0 of
        # a whole prompt comes from the newest prefill record; any other
        # token from the unified step being accepted, which is the last
        # one dispatched but one while another is in flight behind it
        jax.effects_barrier()
        if len(seq.tokens) == 1 and seq.work_len <= GEOMETRY["prefill_chunk"]:
            rows.append([r for r in records if r.shape[0] != SLOTS][-1][0])
            return
        steps = [r for r in records if r.shape[0] == SLOTS]
        rows.append(steps[-2 if eng._inflight is not None else -1][seq.slot])

    eng.on_token = on_token
    seq = eng.submit(GenerationRequest(prompt, max_new_tokens=n_new))
    while eng.has_work():
        eng.step()
        if preempt_after is not None and len(seq.tokens) == preempt_after:
            eng._preempt(seq)           # free the slot, recompute later
            preempt_after = None
    assert seq.done and len(seq.tokens) == n_new == len(rows)
    return eng, list(seq.tokens), np.stack(rows)


def _worst_deviation(model, prompt, tokens, rows):
    """max |engine logits - reference logits| over the generated positions,
    as a share of the reference's largest |logit|."""
    ids = np.asarray([prompt + tokens], np.int32)
    at = np.asarray([[len(prompt) - 1 + k for k in range(len(tokens))]])
    ref = np.asarray(reference_olmoe.logits_at(
        reference_olmoe.weights_of(model),
        reference_olmoe.hyper_of(model.config), ids, at))[0]
    return float(np.abs(rows - ref).max() / np.abs(ref).max())


CASES = {
    # name: (prompt length, new tokens, preempt after, attention path)
    "whole_prompt_then_decode": (21, 10, None, "jnp"),
    "whole_prompt_pallas_interpret": (13, 4, None, "pallas"),
    "chunked_through_unified_step": (75, 6, None, "jnp"),
    "preempted_and_recomputed": (21, 9, 4, "jnp"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_equal_reference(case, monkeypatch):
    n_prompt, n_new, preempt, attention = CASES[case]
    model = _model(attention)
    prompt = _prompt(n_prompt)
    eng, tokens, rows = _serve_recording_logits(
        model, prompt, n_new, monkeypatch, preempt_after=preempt)
    assert _worst_deviation(model, prompt, tokens, rows) <= TOLERANCE
    if case == "chunked_through_unified_step":
        # three chunks through the unified step, no whole-prompt program
        assert eng.stats["prefill_chunks"] == 3
        assert eng.prefill_programs_asked == 0
    if preempt is not None:
        assert eng.stats["preemptions"] == 1 and eng.stats["restores"] == 1
    # the routing summary rode the fetches: K pairs for every live token
    k = model.config.num_experts_per_tok
    calls = eng.stats["moe_layer_calls"]
    assert calls % model.config.num_hidden_layers == 0 and calls > 0
    assert eng.stats["moe_pairs"] % k == 0 and eng.stats["moe_pairs"] > 0
    assert 0 < eng.stats["moe_experts_touched"] \
        <= calls * model.config.num_experts


def _wrong_expert(h, router, w_gate, w_up, w_down, **kw):
    import jax.numpy as jnp
    # (the weights are stacks over layers: roll the expert axis)
    return moe_mod.moe_ffn(h, router, jnp.roll(w_gate, 1, 1),
                           jnp.roll(w_up, 1, 1), jnp.roll(w_down, 1, 1),
                           **kw)


def _renormalised(h, *w, **kw):
    return moe_mod.moe_ffn(h, *w, **{**kw, "renormalize": True})


def _dropped_token(h, *w, live, **kw):
    # the last live row of the buffer loses its experts
    import jax.numpy as jnp
    flat = live.reshape(-1)
    last = jnp.max(jnp.where(flat, jnp.arange(flat.shape[0]), -1))
    dropped = (flat & (jnp.arange(flat.shape[0]) != last)).reshape(live.shape)
    return moe_mod.moe_ffn(h, *w, live=dropped, **kw)


WRONG = {
    "wrong_expert": ("moe_ffn", _wrong_expert),
    "no_qk_norm": ("_qk_norm", lambda q, k, *_: (q, k)),
    "renormalised_weights": ("moe_ffn", _renormalised),
    "dropped_token": ("moe_ffn", _dropped_token),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_wrong_variant_fails(variant, monkeypatch):
    """Each deliberately wrong layer moves the logits a hundred times past
    the tolerance: the comparison above would fail on it."""
    name, fn = WRONG[variant]
    monkeypatch.setattr(decode_mod, name, fn)
    model = _model()
    prompt = _prompt(21)
    _, tokens, rows = _serve_recording_logits(model, prompt, 6, monkeypatch,
                                              fresh=True)
    assert _worst_deviation(model, prompt, tokens, rows) > 1e-2


SWITCHES = {
    "quantize_weights": dict(quantize_weights=True),
    "quantize_activations": dict(quantize_weights=True,
                                 quantize_activations=True),
    "tp > 1": dict(tp=2),
    "decode_ticks > 1": dict(decode_ticks=4),
    "spec_decode": dict(spec_decode=True),
    "decode_chunk > 1": dict(decode_chunk=8),
    "prefix_cache": dict(prefix_cache=True),
}


@pytest.mark.parametrize("switch", sorted(SWITCHES))
def test_unsupported_switch_raises(switch):
    model = _model()
    kw = {**GEOMETRY, **SWITCHES[switch]}
    with pytest.raises(ValueError) as e:
        serving_support.engine_as_given(model, **kw)
    assert "OlmoeForCausalLM" in str(e.value) and switch in str(e.value)


def test_routing_changes_share_one_program():
    """(d) compile-once: prompts that route differently, groups of one and
    two, decode-only and chunk-carrying steps: one unified-step program a
    packed size (the second built when the first chunk is planned), and
    whole-prompt programs bounded by the (group, bucket) grid."""
    model = _model()
    # programs of its own (jnp path): the counts are of what THIS engine
    # has built so far, the second size only once a chunk is planned
    eng = _engine(model, jit_cache={})
    eng.generate([GenerationRequest(_prompt(9, 1), max_new_tokens=5)])
    touched = eng.stats["moe_experts_touched"]
    assert eng.decode_compilations() == 1 and eng.prefill_compilations() == 1
    eng.generate([GenerationRequest(_prompt(12, 2), max_new_tokens=5),
                  GenerationRequest(_prompt(70, 3), max_new_tokens=5)])
    assert eng.stats["moe_experts_touched"] > touched
    assert eng.decode_compilations() == 2       # the prompt of 70 chunked
    assert eng.prefill_compilations() == 1      # same (1, 16) bucket


def test_generate_matches_forward_greedy():
    model = _model()
    ids = np.asarray([_prompt(10, 4), _prompt(10, 5)], np.int32)
    out = np.asarray(model.generate(ids, max_new_tokens=5).value)
    for b in range(2):
        seq = np.concatenate([ids[b], out[b]])
        logits = np.asarray(model.forward(seq[None]).value)[0]
        assert [int(logits[9 + i].argmax()) for i in range(5)] \
            == out[b].tolist()


@pytest.mark.parametrize("make", [
    lambda: serving_support.model("llama", seed=7),
    lambda: serving_support.model("llama", seed=7, tie_word_embeddings=True),
    _model], ids=["llama", "llama_tied", "olmoe"])
def test_decode_params_stack_over_layers(make):
    """What the layer scan assumes of every model the engine accepts: each
    per-layer entry of its decode parameters leads with
    ``num_hidden_layers``; a third model fails here, not inside a trace."""
    model = make()
    params, tied = model.decode_params()
    names, stack, experts = decode_mod._layer_stack(params)
    assert set(names) - {"layer"} \
        == set(params) - {"embed", "final_norm", "lm_head"}
    # a routed FFN's expert stacks are not scanned (their places hold
    # None): the grouped matmul reads its layer of the whole stack
    leaves = [(n, a) for n, a in zip(names, stack) if a is not None] \
        + list(zip(decode_mod._EXPERT_KEYS, experts or ()))
    assert {n for n, _ in leaves} == set(names)
    for name, leaf in leaves:
        assert leaf.shape[0] == model.config.num_hidden_layers, name
    assert tied == (model.lm_head is None)
    _engine(model)                      # and it is accepted


# ----------------------------------------------------------- over HTTP
@pytest.fixture(scope="module")
def http_server():
    from paddle_tpu.serving.server import serve
    model = _model()
    srv = serve(model, port=0, num_slots=2, max_seq_len=96, prefill_chunk=32,
                model_name="olmoe-tiny-test")
    yield model, srv
    srv.shutdown(drain=False, timeout=30)


def _complete(srv, prompt, n):
    import json
    import urllib.request
    req = urllib.request.Request(
        srv.url + "/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": n}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.load(r)["choices"][0]["token_ids"]


@pytest.mark.parametrize("n_prompt", [11, 70], ids=["whole", "chunked"])
def test_http_completion_equals_the_direct_engine(http_server, n_prompt):
    """``serve(OlmoeForCausalLM(...))``: the same gateway, scheduler, paged
    pool and unified step as Llama; the HTTP stream is the engine's."""
    model, srv = http_server
    prompt = _prompt(n_prompt, 11)
    direct = _engine(model, num_slots=2,
                     jit_cache=model.__dict__["_serving_jit"])   # serve()'s
    want = direct.generate([GenerationRequest(prompt, max_new_tokens=6)])[0]
    assert _complete(srv, prompt, 6) == want.tolist()


def test_metrics_carry_the_routing_counters(http_server):
    import urllib.request
    _, srv = http_server
    _complete(srv, _prompt(9, 12), 4)
    with urllib.request.urlopen(srv.url + "/metrics", timeout=60) as r:
        text = r.read().decode()
    values = {}
    for line in text.splitlines():
        if line.startswith("serving_moe_"):
            name, _, val = line.rpartition(" ")
            values[name] = float(val)
    assert set(values) == {"serving_moe_pairs_total",
                           "serving_moe_picks_total",
                           "serving_moe_experts_touched_total",
                           "serving_moe_layer_calls_total",
                           "serving_moe_compact_calls_total",
                           "serving_moe_max_expert_pairs_total"}
    assert values["serving_moe_layer_calls_total"] >= 2 * 4
    # dropless: K pairs for every live token of every layer call
    assert values["serving_moe_pairs_total"] % 2 == 0
    # every expert is held here: each pick made a pair
    assert values["serving_moe_picks_total"] \
        == values["serving_moe_pairs_total"]
    # ... so no call has a smaller buffer than every pick's to run on
    assert values["serving_moe_compact_calls_total"] == 0
    assert values["serving_moe_max_expert_pairs_total"] \
        <= values["serving_moe_pairs_total"]
    assert srv.gateway.engine.decode_compilations() == 2


def test_a_dense_models_metrics_have_no_routing_series():
    from paddle_tpu.serving.server import serve
    srv = serve(serving_support.model("llama", seed=1), port=0, num_slots=2,
                max_seq_len=64)
    try:
        import urllib.request
        with urllib.request.urlopen(srv.url + "/metrics", timeout=60) as r:
            assert "serving_moe_" not in r.read().decode()
    finally:
        srv.shutdown(drain=False, timeout=30)


def test_server_presets_build_the_model():
    from paddle_tpu.serving.server.__main__ import PRESETS, build_model
    assert {"olmoe-tiny", "olmoe1b7b-8of16"} <= set(PRESETS)
    model = build_model("olmoe-tiny", "jnp", seed=0)
    assert isinstance(model, OlmoeForCausalLM)
    assert model.config.num_experts == 8
