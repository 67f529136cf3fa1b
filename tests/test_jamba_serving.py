"""Jamba (AI21-Jamba2-3B) through the serving engine (ISSUE 50): periods of
Mamba-1 layers with three inner RMSNorms around ONE attention layer whose
place in the period the configuration gives, a multi-query pool of the
attention layers only beside a state store of all the Mamba layers.

The engine against the plain reference ON LOGITS
(``benchmark/reference_jamba.py``: the selective scan token by token, a masked
softmax, float32) at ``jamba_tiny`` (periods of 4 with the attention layer at
2, 5 query heads on 1 KV head): every token the engine generates is produced
from logits that equal the reference's full forward at that position, for
whole-prompt prefill then decode, for a prompt through three chunks, for a
chunked prompt beside another sequence's decode rows, in a slot a longer
sequence used before. Tolerance 1e-4 of the largest logit: float32 on both
sides (conftest sets matmul precision ``highest``). Then what the comparison
would catch: the inner norms dropped, the attention layer at another place in
the period, a convolution tail lost at a chunk boundary; and a bfloat16 state,
which it would not, refused by its dtype. The module's engines share one set
of compiled programs (``JIT``), with the logits' recorder inside them.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import jamba as jamba_mod
from paddle_tpu.models.jamba import JambaConfig
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import drain as _run, token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_jamba as ref  # noqa: E402
import reference_phi4_flash as ref_phi4  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
CHUNK = 32
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=128, decode_chunk=1,
                prefill_chunk=CHUNK, prefix_block_size=8)


def _model(kernel="jnp", seed=7, **kw):
    return serving_support.model("jamba", seed=seed, decode_attention=kernel,
                                 **kw)


@pytest.fixture(scope="module")
def model():
    return _model()


#: the programs of the module's one jnp model, compiled once: every test's
#: engine shares them (and the recorder inside them, ``_recorder``)
JIT = {}


def _reference_logits(model, ids, at):
    return serving_support.reference_logits(ref, model, ids, at,
                                            GEOMETRY["max_seq_len"])


def _deviation(model, seq, rows):
    return serving_support.deviation(ref, model, seq, rows,
                                     GEOMETRY["max_seq_len"])


@pytest.fixture(scope="module")
def _recorder():
    """The module's one recorder: the shared programs (``JIT``) were traced
    with it inside, so it is patched in for the module's whole life."""
    mp = pytest.MonkeyPatch()
    yield serving_support.LogitsRecorder(mp, SLOTS, CHUNK)
    mp.undo()


@pytest.fixture
def rec(_recorder):
    return _recorder.clear()


def _engine(model, rec, jit_cache=None):
    """The shared helper at this file's geometry, on the module's recorded
    programs (``JIT``) and watched by the recorder inside them."""
    eng = serving_support.watch_prefill_programs(
        serving_support.engine_as_given(
            model, jit_cache=JIT if jit_cache is None else jit_cache,
            **GEOMETRY))
    rec.watch(eng)
    return eng


CASES = {
    # name: (prompt length, new tokens); a chunk is 32
    "whole_prompt_then_decode": (21, 12),
    "three_chunks_then_decode": (75, 6),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_equal_reference(case, model, rec):
    n_prompt, n_new = CASES[case]
    eng = _engine(model, rec)
    seq = eng.submit(GenerationRequest(_prompt(n_prompt),
                                       max_new_tokens=n_new))
    _run(eng)
    assert seq.done and len(seq.tokens) == n_new
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE
    if n_prompt > CHUNK:
        # chunks through the unified step, no whole-prompt program
        assert eng.stats["prefill_chunks"] == -(-n_prompt // CHUNK)
        assert eng.prefill_programs_asked == 0
    spans = max(1, eng.stats["prefill_chunks"])
    assert eng.stats["state_rows"] == spans + n_new - 1


def test_chunks_beside_decode_rows(model, rec):
    """A prompt whose chunk boundaries lie inside it, chunked while another
    sequence decodes: chunk rows and decode rows of different slots in one
    packed buffer (the chunk scan and the in-place update in one program),
    then one row a slot."""
    eng = _engine(model, rec)
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((11, 9), (70, 5))]
    _run(eng)
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


def test_kernels_interpreted(rec):
    """The three Pallas kernels in interpret mode through the engine, in ONE
    program: two prompts of two chunks (the scan from a zero state and from
    the store; the ragged kernel at a group of 5 query heads on 1 KV head),
    the second's chunks beside the first's decode row (the in-place update)."""
    model = _model("pallas")
    eng = _engine(model, rec, jit_cache={})
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((40, 2), (41, 1))]
    _run(eng)
    assert eng.decode_compilations() == 1 and eng.prefill_compilations() == 0
    assert eng.stats["state_rows"] == eng.stats["prefill_chunks"] + 1 == 5
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_forward_equals_reference(kernel):
    """The model's own whole-sequence forward (with ``pallas`` the chunk scan
    from a zero state, as whole-prompt prefill runs it) against the reference
    at every position: the two paths agree through it."""
    model = _model(kernel)
    ids = _prompt(40, 3)
    got = np.asarray(model.forward(np.asarray([ids], np.int32)).value)[0]
    want = _reference_logits(model, ids, range(40))
    assert np.abs(got - want).max() / np.abs(want).max() <= TOLERANCE


@pytest.mark.parametrize("second", [40, 9], ids=["chunked", "whole"])
def test_a_reused_slot_starts_from_a_zero_state(second, model, rec):
    """No program zeroes a slot: the second, SHORTER sequence in slot 0 reads
    the logits a fresh engine gives (no stale state or tail), because its
    first span starts at 0."""
    eng = _engine(model, rec)
    first = eng.submit(GenerationRequest(_prompt(90, 1), max_new_tokens=7))
    _run(eng)
    assert first.done and first.slot == 0
    for held in eng.cache.store:    # the slot holds what it held
        assert np.abs(np.asarray(held[:, 0], np.float32)).max() > 0
    seq = eng.submit(GenerationRequest(_prompt(second, 2), max_new_tokens=6))
    _run(eng)
    assert seq.done and seq.slot == 0
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


def test_preempted_and_recomputed(model, rec):
    eng = _engine(model, rec)
    seq = eng.submit(GenerationRequest(_prompt(21), max_new_tokens=9))
    armed = [True]

    def between():
        if armed[0] and len(seq.tokens) == 4:
            eng._drain("preempt")
            eng._preempt(seq)           # free the slot, recompute from 0
            armed[0] = False

    _run(eng, between)
    assert seq.done and len(seq.tokens) == 9
    assert eng.stats["state_restarts_preempt"] == 1
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


# ---------------------------------------------------------- the shared mixer
def _one_layer(model, place="m0"):
    return {k: jnp.asarray(v[0]) for k, v in model._tree(place).items()}


def _mixer_alone(lw, hn, **norm):
    """``decode._mamba_mixer`` on one sequence from a zero start."""
    lengths = jnp.full((1,), hn.shape[1], jnp.int32)
    live = jnp.ones(hn.shape[:2], bool)
    mixer = decode_mod._mamba_rows_mixer(lengths, live, model_ssm(), **norm)
    return mixer(hn, lw)


def model_ssm():
    return jamba_mod.jamba_tiny().ssm._replace(kernel="jnp")


def test_a_tree_without_the_inner_norms_runs_the_plain_mixer(model):
    """The three norms are the TREE's: with their weights the shared mixer is
    the Jamba reference's, without them it is Phi-4-mini-flash's, whose
    program holds no normalisation at all."""
    lw = _one_layer(model)
    hn = jnp.asarray(np.random.RandomState(0).randn(1, 24, 80), jnp.float32)
    eps = float(model.config.rms_norm_eps)
    out, _ = _mixer_alone(lw, hn, eps=eps)
    want = ref.mamba(hn[0], lw, eps)
    assert np.abs(out[0] - want).max() <= 1e-5 * np.abs(want).max()
    plain = {k: v for k, v in lw.items() if not k.endswith("_ln")}
    out, _ = _mixer_alone(plain, hn)
    want_plain, _ = ref_phi4.mamba(hn[0], plain)
    assert np.abs(out[0] - want_plain).max() <= 1e-5 * np.abs(want_plain).max()
    assert np.abs(want - want_plain).max() > 0.1 * np.abs(want).max()
    text = str(jax.make_jaxpr(lambda h: _mixer_alone(plain, h)[0])(hn))
    assert "rsqrt" not in text


# ------------------------------------------------ what the check would catch
def _forward_deviation(model, params, n):
    """``models.jamba``'s forward on the tree ``params`` against the
    reference on the model's own, over ``n`` tokens (a length of its own a
    caller: a program traced with a fault inside is nobody else's)."""
    c = model.config
    ids = _prompt(n, 5)
    got = np.asarray(jamba_mod._forward(
        params, np.asarray([ids], np.int32), nh=c.num_attention_heads,
        nkv=c.num_key_value_heads, hd=c.head_dim, eps=float(c.rms_norm_eps),
        ssm=c.ssm))[0]
    want = _reference_logits(model, ids, range(n))
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_inner_norms_dropped_fails(model, monkeypatch):
    real = decode_mod._mamba_mixer
    monkeypatch.setattr(
        decode_mod, "_mamba_mixer", lambda hn, lw, **kw: real(
            hn, {k: v for k, v in lw.items() if not k.endswith("_ln")}, **kw))
    params, _ = model.decode_params()
    assert _forward_deviation(model, params, 61) > 30 * TOLERANCE


def test_attention_at_the_wrong_offset_fails(model):
    """The attention layer one place early: the same weights, another order
    of the layers."""
    params, _ = model.decode_params()
    assert _forward_deviation(model, params, 62) <= TOLERANCE
    before, after = params["mamba_layers"]
    wrong = dict(params, mamba_layers=(before[:-1], before[-1:] + after))
    assert _forward_deviation(model, wrong, 62) > 30 * TOLERANCE


def test_a_bfloat16_state_is_refused(model):
    """The store's states are float32, and the step program refuses any
    other: rounded to bfloat16 between the steps they move this model's
    logits by 3e-5 of their range (75 tokens, measured here), which no
    comparison on logits would catch."""
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    ss, cs = eng.cache.store
    assert ss.dtype == jnp.float32
    R = SLOTS
    span = dict(seg=jnp.zeros((8,), jnp.int32), pos=jnp.arange(8),
                qstart=jnp.zeros((R,), jnp.int32),
                qlen=jnp.asarray([8, 0, 0], jnp.int32),
                kvlen=jnp.asarray([8, 0, 0], jnp.int32), T=8)
    mixer = decode_mod._mamba_span_mixer(
        model_ssm(), eps=float(model.config.rms_norm_eps), **span)
    hn = jnp.ones((1, 8, model.config.hidden_size), jnp.float32)
    out, _ = mixer(hn, _one_layer(model), 0, ss, cs)
    assert out.shape == hn.shape
    with pytest.raises(TypeError, match="float32"):
        mixer(hn, _one_layer(model), 0, ss.astype(jnp.bfloat16), cs)


def test_a_lost_conv_tail_fails(model, rec):
    eng = _engine(model, rec)
    seq = eng.submit(GenerationRequest(_prompt(75), max_new_tokens=4))

    def between():
        ss, cs = eng.cache.store
        eng.cache.store = (ss, jnp.zeros_like(cs))

    _run(eng, between)
    assert _deviation(model, seq, rec.rows[seq.request_id]) > 10 * TOLERANCE


# ------------------------------------------------------------ the two caches
def test_two_kinds_of_cache(model):
    c = model.config
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    assert (c.num_hidden_layers, c.num_kv_layers, c.num_ssm_layers) == \
        (8, 2, 6)
    # a pool layer an ATTENTION layer, one KV head: a row a token
    assert eng.cache.pool.k.shape[0] == 2 == eng.cache.pool.v.shape[0]
    assert eng.cache.bytes_per_token() == 2 * 2 * c.head_dim * 4
    states, tails = eng.cache.state
    assert states.shape == (6, SLOTS, c.mamba_d_state, c.d_inner)
    assert states.dtype == jnp.float32
    assert tails.shape == (6, SLOTS, c.mamba_d_conv - 1, c.d_inner)
    assert eng.cache.window is None
    assert eng.cache.state_bytes_per_slot == (states[:, 0].size
                                              + tails[:, 0].size) * 4


def test_metrics_carry_the_store_and_the_pool(model):
    from paddle_tpu.serving.server import serve
    server = serve(model, port=0, **{k: v for k, v in GEOMETRY.items()
                                     if k != "decode_chunk"})
    try:
        import urllib.request
        text = urllib.request.urlopen(server.url + "/metrics").read().decode()
    finally:
        server.shutdown()
    for name in ("serving_state_bytes_per_slot", "serving_kv_bytes_per_token",
                 "serving_state_restarts_total"):
        assert name in text, name


@pytest.mark.parametrize("switch", serving_support.OTHER_SWITCHES,
                         ids=lambda s: next(iter(s)))
def test_every_other_switch_raises_by_name(switch, model):
    geometry = {**GEOMETRY, **switch}
    # (one KV head: tensor parallelism is refused before the tree is read)
    match = "num_key_value_heads" if "tp" in switch else "mamba_layers"
    with pytest.raises(ValueError, match=match) as e:
        serving_support.engine_as_given(model, **geometry)
    assert all(name in str(e.value) for name in switch)


def test_dispatch_args_count_the_two_kinds_of_layer(model):
    eng = serving_support.engine_as_given(
        model, **{**GEOMETRY, "max_seq_len": 1024})
    qstart = np.array([0, 1, 0], np.int32)
    qlen = np.array([1, 32, 0], np.int32)
    kvlen = np.array([900, 800, 0], np.int32)
    args = eng._dispatch_args(qstart, qlen, kvlen, eng._token_budget, 1, 1,
                              32)
    assert args["state_rows"] == 2 and args["scan_spans"] == 1
    assert args["scan_tokens"] == 32 and args["kv_tokens"] == 900 + 800
    assert "cross_rows" not in args and "window_kv_tokens" not in args


def test_the_decode_only_program_has_no_chunk_scan():
    """The plan gives the small program one-token spans only, so it launches
    the in-place update and not the chunked scan (traced, never run)."""
    # programs of its own: the module's recorder may be patched in
    eng = serving_support.engine_as_given(_model("pallas"), jit_cache={},
                                          **GEOMETRY)
    R = eng.num_slots

    def zeros(shape, dtype=np.int32):
        return np.zeros(shape, dtype)

    kernels = {}
    for T in eng.step_rows:
        eng._ragged_fn(1, T)
        (fn,) = [f for k, f in eng._jit.items()
                 if k[0] == "ragged" and k[3] == T]
        text = str(jax.make_jaxpr(fn)(
            eng._params, *eng.cache.kv_args(), eng.cache.tables, zeros(T),
            zeros(T), zeros(T), zeros(R), zeros(R), zeros(R), zeros(R),
            eng._keys, zeros(R, np.float32), zeros(R), eng._no_toks,
            zeros(R), zeros((R, 2), np.uint32), zeros(R), eng.cache.store))
        kernels[T] = (text.count("ssm_chunk_scan"),
                      text.count("ssm_recurrent_update"))
    small, large = eng.step_rows
    # (the printed jaxpr names a kernel once a distinct call of the scanned
    # period's body)
    assert kernels[small][0] == 0 < kernels[small][1]
    assert kernels[large][0] > 0 and kernels[large][1] == kernels[small][1]


def test_config_refuses_what_the_forward_cannot_run():
    with pytest.raises(ValueError, match="whole periods"):
        JambaConfig(num_hidden_layers=27)
    with pytest.raises(ValueError, match="attn_layer_offset"):
        JambaConfig(attn_layer_offset=14)
    with pytest.raises(ValueError, match="dense MLP"):
        JambaConfig(num_experts=16)
    c = JambaConfig()
    assert (c.d_inner, c.mamba_dt_rank, c.head_dim) == (5120, 160, 128)
    assert (c.num_ssm_layers, c.num_kv_layers, c.num_periods) == (26, 2, 2)
