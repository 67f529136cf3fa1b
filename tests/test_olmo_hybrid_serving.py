"""Olmo Hybrid through the serving engine (ISSUE 33): Gated DeltaNet layers
whose recurrent state lives in a store by slot beside the KV pool of every
fourth layer.

The engine against the plain reference ON LOGITS
(``benchmark/reference_olmo_hybrid.py``: the delta rule token by token, float32):
every token the engine generates is produced from logits that equal the
reference's full forward at that position, for whole-prompt prefill then
decode, for chunked prefill with the state carried between chunks, for two
requests of unequal length in one step, in a slot another sequence used
before, after preemption by recompute and after a fence that raised.
Tolerance 1e-4 of the largest logit: float32 on both sides (conftest sets
matmul precision ``highest``); what is left is summation order and the chunked
form's algebra, about 1e-5. ``test_wrong_variant_fails`` shows a ``beta``
without its 2, a dropped decay, a lost convolution tail and a state that is not
zeroed at a sequence's start each failing.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.olmo_hybrid import (FULL, LINEAR, OlmoHybridConfig,
                                           OlmoHybridForCausalLM,
                                           olmo_hybrid_tiny)
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import drain as _run, token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_olmo_hybrid as ref  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=96, decode_chunk=1,
                prefill_chunk=32)


def _model(kernel="jnp"):
    return serving_support.model("olmo_hybrid", seed=7,
                                 decode_attention=kernel)


def _engine(model, **kw):
    """The shared helper at this file's geometry (3 slots, chunks of 32, the
    engine's own block)."""
    return serving_support.engine_as_given(model, **{**GEOMETRY, **kw})


class _Recorder:
    """Every program's logits (``decode._head_logits``), in dispatch order,
    and for every token a sequence is given the row it was sampled from.
    One for the module: the programs a recording test traces hold it, and
    are kept (``programs``) for the recording tests that follow."""

    def __init__(self):
        self.records, self.rows, self.jit = [], {}, {}
        real = decode_mod._head_logits

        def recording(last_h, head):
            logits = real(last_h, head)
            jax.debug.callback(lambda x: self.records.append(np.asarray(x)),
                               logits, ordered=True)
            return logits

        self.recording = recording

    def engine(self, model):
        """An engine on the recorded programs of ``model``, watched. They are
        this file's own: nobody else may run a program with the recorder in
        it."""
        eng = serving_support.watch_prefill_programs(_engine(
            model, jit_cache=self.jit.setdefault(
                model.config.decode_attention, {})))
        self.watch(eng)
        return eng

    def watch(self, eng):
        def on_token(seq, _tok):
            # token 0 of a whole prompt comes from the newest prefill
            # record; any other token from the unified step being
            # accepted: the last one dispatched, or the last but one while
            # another is in flight behind it
            jax.effects_barrier()
            rows = self.rows.setdefault(seq.request_id, [])
            whole = seq.work_len <= GEOMETRY["prefill_chunk"]
            if len(seq.tokens) == 1 and whole:
                group = [r for r in self.records if r.shape[0] != SLOTS][-1]
                rows.append(group[0])    # groups of one in these tests
                return
            steps = [r for r in self.records if r.shape[0] == SLOTS]
            rows.append(steps[-2 if eng._inflight is not None
                              else -1][seq.slot])

        eng.on_token = on_token


def _deviation(model, seq, rows):
    """max |engine logits - reference logits| over the generated positions,
    as a share of the reference's largest |logit|."""
    prompt, tokens = list(seq.prompt), list(seq.tokens)
    ids = np.asarray([prompt + tokens], np.int32)
    at = np.asarray([[len(prompt) - 1 + k for k in range(len(tokens))]])
    want = np.asarray(ref.logits_at(ref.weights_of(model),
                                    ref.hyper_of(model.config), ids, at))[0]
    assert len(rows) == len(tokens)
    return float(np.abs(np.stack(rows) - want).max() / np.abs(want).max())


_RECORDER = _Recorder()


@pytest.fixture
def rec(monkeypatch):
    """The module's recorder, emptied, and patched in for this test."""
    del _RECORDER.records[:]
    _RECORDER.rows.clear()
    monkeypatch.setattr(decode_mod, "_head_logits", _RECORDER.recording)
    return _RECORDER


CASES = {
    # name: (prompt length, new tokens, kernel)
    "whole_prompt_then_decode": (21, 10, "jnp"),
    "whole_prompt_kernels_interpreted": (13, 4, "pallas"),
    "chunked_then_decode": (75, 6, "jnp"),
    "chunked_kernels_interpreted": (70, 3, "pallas"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_equal_reference(case, rec):
    n_prompt, n_new, kernel = CASES[case]
    model = _model(kernel)
    eng = rec.engine(model)
    seq = eng.submit(GenerationRequest(_prompt(n_prompt), max_new_tokens=n_new))
    _run(eng)
    assert seq.done and len(seq.tokens) == n_new
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE
    if n_prompt > GEOMETRY["prefill_chunk"]:
        # chunks through the unified step, no whole-prompt program
        assert eng.stats["prefill_chunks"] == -(-n_prompt // 32)
        assert eng.prefill_programs_asked == 0
    # one state row a span a program: the prompt's spans and the decode rows
    spans = max(1, eng.stats["prefill_chunks"])
    assert eng.stats["state_rows"] == spans + n_new - 1


def test_two_requests_of_unequal_length_share_steps(rec):
    """A chunked prompt and a whole one, decoding together: chunks and decode
    rows of different slots in one packed buffer."""
    model = _model()
    eng = rec.engine(model)
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((70, 5), (11, 9))]
    _run(eng)
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


@pytest.mark.parametrize("second", [40, 9], ids=["chunked", "whole"])
def test_a_reused_slot_starts_from_a_zero_state(second, rec):
    """No program zeroes a slot: the second sequence in slot 0 reads the
    logits a fresh engine gives, because its first span starts at 0."""
    model = _model()
    eng = rec.engine(model)
    first = eng.submit(GenerationRequest(_prompt(50, 1), max_new_tokens=7))
    _run(eng)
    assert first.done and first.slot == 0
    state0 = np.asarray(eng.cache.state[0][:, 0])
    assert np.abs(state0).max() > 0            # the slot holds what it held
    seq = eng.submit(GenerationRequest(_prompt(second, 2), max_new_tokens=6))
    _run(eng)
    assert seq.done and seq.slot == 0
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


def test_preempted_and_recomputed(rec):
    model = _model()
    eng = rec.engine(model)
    seq = eng.submit(GenerationRequest(_prompt(21), max_new_tokens=9))
    armed = [True]

    def between():
        if armed[0] and len(seq.tokens) == 4:
            eng._drain("preempt")
            eng._preempt(seq)           # free the slot, recompute from 0
            armed[0] = False

    _run(eng, between)
    assert seq.done and len(seq.tokens) == 9
    assert eng.stats["preemptions"] == 1 and eng.stats["restores"] == 1
    assert eng.stats["state_restarts_preempt"] == 1
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


class _Broken:
    def __init__(self, real):
        self.real = real

    def __array__(self, *a, **kw):
        raise RuntimeError("device lost")


def test_a_fence_that_raises_restarts_from_position_zero(rec):
    """The dropped programs applied their tokens to the recurrent states:
    every sequence they carried is recomputed from position 0, and every
    stream reads the logits of the undisturbed one."""
    model = _model()
    eng = rec.engine(model)
    real_fn, count = eng._ragged_fn, [0]

    def ragged_fn(n, rows):
        fn = real_fn(n, rows)

        def call(*args):
            out = list(fn(*args))
            count[0] += 1
            if count[0] == 3:       # chunks and decode rows are in it
                out[2] = _Broken(out[2])
            return tuple(out)
        return call

    eng._ragged_fn = ragged_fn
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((20, 8), (9, 7), (90, 5))]   # one bucket each
    faults = 0
    while eng.has_work():
        try:
            eng.step()
        except RuntimeError:
            faults += 1
            assert eng._inflight is None
    assert faults == 1 and eng.stats["drains_fault"] == 1
    assert eng.stats["state_restarts_fault"] >= 2
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


# ------------------------------------------------ what the check would catch
_GATES = decode_mod.gdn_gates


def _no_doubling(ab, a_log, dt_bias, neg_eigval):
    return _GATES(ab, a_log, dt_bias, False)


def _no_decay(ab, a_log, dt_bias, neg_eigval):
    g, beta = _GATES(ab, a_log, dt_bias, neg_eigval)
    return jnp.zeros_like(g), beta


WRONG = ("beta_without_its_2", "dropped_decay", "lost_conv_tail",
         "state_never_zeroed")


@pytest.mark.parametrize("variant", WRONG)
def test_wrong_variant_fails(variant, rec, monkeypatch):
    model = _model()
    if variant == "beta_without_its_2":
        monkeypatch.setattr(decode_mod, "gdn_gates", _no_doubling)
    if variant == "dropped_decay":
        monkeypatch.setattr(decode_mod, "gdn_gates", _no_decay)
    if variant == "lost_conv_tail":
        eng = rec.engine(model)         # the fault is made on the host
    else:
        # programs of its own: the wrong function is patched in before the
        # trace, and nobody else may run what is traced with it
        eng = _engine(model, jit_cache={})
        rec.watch(eng)
    if variant == "state_never_zeroed":
        eng.cache.state = tuple(jnp.ones_like(a) for a in eng.cache.state)
        real_ref = decode_mod.gdn_reference
        monkeypatch.setattr(
            decode_mod, "gdn_reference",
            lambda *a, first, **kw: real_ref(
                *a, first=jnp.zeros_like(first), **kw))
    seq = eng.submit(GenerationRequest(_prompt(75), max_new_tokens=4))
    if variant == "lost_conv_tail":
        def between():      # the tails vanish at every chunk boundary
            ss, cs = eng.cache.state
            eng.cache.state = (ss, jnp.zeros_like(cs))
        _run(eng, between)
    else:
        _run(eng)
    assert _deviation(model, seq, rec.rows[seq.request_id]) > 100 * TOLERANCE


# ------------------------------------------------------------ the two stores
def test_the_pool_holds_the_full_layers_only():
    model = _model()
    c = model.config
    eng = _engine(model)
    assert c.num_hidden_layers == 8 and c.num_kv_layers == 2
    assert eng.cache.pool.k.shape[0] == 2 == eng.cache.pool.v.shape[0]
    per_token = 2 * 2 * c.num_key_value_heads * c.head_dim * 4
    assert eng.cache.bytes_per_token() == per_token
    states, tails = eng.cache.state
    g = c.gdn
    # the store's layout is its kernels' to say: no minor dimension the
    # device would pad to whole lanes
    assert states.shape == (6, SLOTS, g.dk, g.heads * g.dv)
    assert states.dtype == jnp.float32
    assert tails.shape == (6, SLOTS, g.conv - 1, c.conv_channels)
    per_slot = 6 * (g.heads * g.dk * g.dv * 4 + 3 * c.conv_channels * 4)
    assert eng.cache.state_bytes_per_slot == per_slot
    occ = eng.cache.occupancy_bytes()
    assert occ["capacity_state"] == SLOTS * per_slot and occ["used_state"] == 0
    # a dense model has no store and pays nothing for it
    plain = _engine(serving_support.model("llama", seed=0))
    assert plain.cache.state is None
    assert plain.cache.state_bytes_per_slot == 0


def test_the_published_sizes():
    """The cell's configuration: 61,440 B a token in the pool and 27.4 MB a
    slot in the store, reckoned from the shapes (nothing is allocated)."""
    c = OlmoHybridConfig(num_hidden_layers=16,
                         layer_types=([LINEAR] * 3 + [FULL]) * 4)
    assert (c.num_kv_layers, c.num_linear_layers) == (4, 12)
    assert c.num_kv_layers * 2 * c.num_key_value_heads * c.head_dim * 2 \
        == 61440
    g = c.gdn
    assert c.conv_channels == 11520
    assert 12 * (g.heads * g.dk * g.dv * 4 + 3 * 11520 * 2) == 27371520


@pytest.mark.parametrize("types", [
    [LINEAR] * 3 + [FULL] + [LINEAR] * 3,            # not whole periods
    [FULL, LINEAR, LINEAR, LINEAR] * 2,              # a period ends in full
    [LINEAR] * 8,                                    # no full layer
])
def test_layer_types_must_be_whole_periods(types):
    with pytest.raises(ValueError, match="whole periods"):
        olmo_hybrid_tiny(layer_types=types, num_hidden_layers=len(types))


SWITCHES = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_dtype": dict(kv_dtype="int8"),
    "tp": dict(tp=2),
    "spec_decode": dict(spec_decode=True),
    "decode_ticks": dict(decode_ticks=4),
    "decode_chunk": dict(decode_chunk=4),
    "quantize_weights": dict(quantize_weights=True),
}


@pytest.mark.parametrize("switch", sorted(SWITCHES))
def test_unsupported_switch_raises(switch):
    kw = {**GEOMETRY, **SWITCHES[switch]}
    with pytest.raises(ValueError, match="OlmoHybridForCausalLM"):
        serving_support.engine_as_given(_model(), **kw)


def test_generate_matches_forward_greedy():
    model = _model()
    prompt = np.asarray([_prompt(17)], np.int32)
    out = np.asarray(model.generate(prompt, max_new_tokens=6).value)[0]
    logits = np.asarray(model.forward(out[None]).value)[0]
    want = logits[16:-1].argmax(-1)
    assert out[17:].tolist() == want.tolist()


def _unified_step_text(eng):
    """The lowered text of the engine's unified step at its larger packed
    size (a hybrid model's store is its last argument)."""
    R, T = SLOTS, eng._token_budget
    i32 = np.int32
    args = (eng._params, *eng.cache.kv_args(), eng.cache.tables,
            np.zeros(T, i32), np.full(T, R, i32), np.zeros(T, i32),
            np.zeros(R, i32), np.zeros(R, i32), np.zeros(R, i32),
            np.zeros(R, i32), eng._keys, np.zeros(R, np.float32),
            np.zeros(R, i32), eng._no_toks, np.zeros(R, i32),
            np.zeros((R, 2), np.uint32), np.zeros(R, i32),
            *((eng.cache.state,) if eng._stateful else ()))
    return eng._ragged_fn(1, T).lower(*args).as_text()


@pytest.mark.parametrize("arch", ["llama", "olmoe", "deepseek_v2"])
def test_other_models_programs_take_no_store(arch):
    """Programs of models without the new keys do not change: their unified
    step is lowered with the arguments it had (no store), runs no kernel of
    the delta rule, and returns what it returned."""
    eng = _engine(serving_support.model(arch, seed=0))
    assert not eng._stateful and "gdn" not in eng._fn_consts()
    assert "gdn_" not in _unified_step_text(eng)


@pytest.mark.parametrize("make", [
    lambda: serving_support.model("llama", seed=0),     # "pallas"
    lambda: _model("pallas"),
], ids=["tiny_mistral", "tiny_olmo_hybrid"])
def test_unified_step_makes_no_wide_query(make):
    """The lowered unified step holds no array with ``Hkv * D`` values a
    (token, head): the query goes into the ragged kernel head-major, two
    transposes of ``T * H * D`` elements, and neither a block-diagonal wide
    query nor a wide output ``[T * H, KD]`` is made on the way (for a head
    count padded to whole sublane groups either, the parent's 30 -> 32)."""
    import re
    eng = _engine(make())
    c, T = eng.config, eng._token_budget
    text = _unified_step_text(eng)
    nh, kd = c.num_attention_heads, c.num_key_value_heads * c.head_dim
    wide = {T * rows * kd for rows in (nh, -(-nh // 8) * 8)}
    shapes = {tuple(int(d) for d in m.split("x")[:-1])
              for m in re.findall(r"tensor<((?:\d+x)+[a-z]+\d*)>", text)}
    # (arrays whose minor dim is a head's or a pool row's: the FFN's
    # [T, intermediate] may have as many elements)
    sizes = {int(np.prod(sh)) for sh in shapes
             if sh[-1] in (c.head_dim, kd)}
    assert T * nh * c.head_dim in sizes         # the query itself is there
    assert not wide & sizes, sorted(wide & sizes)


# ----------------------------------------------------------- over HTTP
@pytest.fixture(scope="module")
def http_server():
    from paddle_tpu.serving.server import serve
    model = _model()
    srv = serve(model, port=0, num_slots=2, max_seq_len=96, prefill_chunk=32,
                model_name="olmohybrid-tiny-test")
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
    """``serve(OlmoHybridForCausalLM(...))`` with no other switch: the same
    gateway, scheduler and unified step; the HTTP stream is the engine's."""
    model, srv = http_server
    prompt = _prompt(n_prompt, 11)
    direct = _engine(model, num_slots=2,
                     jit_cache=model.__dict__["_serving_jit"])   # serve()'s
    want = direct.generate([GenerationRequest(prompt, max_new_tokens=6)])[0]
    assert _complete(srv, prompt, 6) == want.tolist()


def test_metrics_carry_the_state_series(http_server):
    import urllib.request
    model, srv = http_server
    _complete(srv, _prompt(9, 12), 4)
    with urllib.request.urlopen(srv.url + "/metrics", timeout=60) as r:
        text = r.read().decode()
    values = {}
    for line in text.splitlines():
        if line.startswith("serving_state_"):
            name, _, val = line.rpartition(" ")
            values[name] = float(val)
    c, g = model.config, model.config.gdn
    per_slot = c.num_linear_layers * (g.heads * g.dk * g.dv * 4
                                      + 3 * c.conv_channels * 4)
    assert values["serving_state_bytes_per_slot"] == per_slot
    assert values["serving_state_rows_total"] >= 4
    assert values['serving_state_restarts_total{reason="fault"}'] == 0
    assert values['serving_state_restarts_total{reason="preempt"}'] == 0
    # the pool's gauge counts the two full layers only
    per_token = 2 * 2 * c.num_key_value_heads * c.head_dim * 4
    line = next(ln for ln in text.splitlines()
                if ln.startswith("serving_kv_bytes_per_token"))
    assert float(line.rpartition(" ")[2]) == per_token


def test_server_presets_build_the_model():
    from paddle_tpu.serving.server.__main__ import PRESETS, build_model
    assert {"olmohybrid-tiny", "olmohybrid7b-16of32"} <= set(PRESETS)
    model = build_model("olmohybrid-tiny", "jnp", seed=0)
    assert isinstance(model, OlmoHybridForCausalLM)
    assert model.config.num_kv_layers == 2
