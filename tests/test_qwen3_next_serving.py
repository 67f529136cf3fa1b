"""Qwen3-Next through the serving engine (ISSUE 54): periods of Gated DeltaNet
layers with more value heads than key heads and then one gated full-attention
layer with a head's norm and a partial rotation, every layer followed by a
routed FFN with a gated shared expert; a float32 state by slot beside a KV pool
of the full layers only.

The engine against the plain reference ON LOGITS
(``benchmark/reference_qwen3_next.py``: the recurrence token by token, a
masked softmax, every held expert over every row, float32): every token the
engine generates is produced from logits that equal the reference's full
forward at that position, for whole-prompt prefill then decode, for a prompt
through three chunks (a chunk boundary inside the prompt: state and
convolution tail carried), in a slot a longer sequence used before. Tolerance
1e-4 of the largest logit: float32 on both sides (conftest sets matmul
precision ``highest``). Then each particular of the model by a fault that must
fail, the stores' geometry and dtype, the eight shares of a layer's FFN adding
up to the uncut layer, and every switch whose program was not taught the
layers raising. The module's engines share one set of compiled programs
(``JIT``), with the logits' recorder inside them.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import gated_delta_rule as gdr
from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models import qwen3_next as mod
from paddle_tpu.models.qwen3_next import Qwen3NextConfig
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import drain as _run, token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_qwen3_next as ref  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
CHUNK = 32
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=128, decode_chunk=1,
                prefill_chunk=CHUNK, prefix_block_size=8)


def _model(kernel="jnp", seed=7, **kw):
    return serving_support.model("qwen3_next", seed=seed,
                                 decode_attention=kernel, **kw)


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
    # eight routed FFNs a program call; 4 of the router's 8 experts held
    assert eng.stats["moe_layer_calls"] % 8 == 0
    assert 0 < eng.stats["moe_pairs"] < eng.stats["moe_picks"]


def test_kernels_interpreted_and_a_reused_slot(rec):
    """The Pallas kernels in interpret mode through the engine, in ONE
    program: a prompt of three chunks (the chunk scan from a zero state and
    from the store, at 4 value heads on 2 key heads), then a SHORTER prompt
    of two chunks in the slot it left (no program zeroes a slot: its first
    span starts at 0) beside nothing; the in-place update, the ragged kernel
    at a query group of 2."""
    model = _model("pallas")
    eng = _engine(model, rec, jit_cache={})
    first = eng.submit(GenerationRequest(_prompt(70, 1), max_new_tokens=3))
    _run(eng)
    assert first.done and first.slot == 0
    for held in eng.cache.store:    # the slot holds what it held
        assert np.abs(np.asarray(held[:, 0], np.float32)).max() > 0
    seq = eng.submit(GenerationRequest(_prompt(40, 2), max_new_tokens=3))
    _run(eng)
    assert seq.done and seq.slot == 0
    assert eng.decode_compilations() == 2 and eng.prefill_compilations() == 0
    for s in (first, seq):
        assert _deviation(model, s, rec.rows[s.request_id]) <= TOLERANCE


@pytest.mark.parametrize("kernel", ["jnp", "pallas"])
def test_forward_equals_reference(kernel):
    """The model's own whole-sequence forward (with ``pallas`` the chunk
    scan from a zero state, as whole-prompt prefill runs it) against the
    reference at every position, and its picks against the reference's."""
    model = _model(kernel)
    ids = _prompt(40, 3)
    logits, picks = model.forward(np.asarray([ids], np.int32),
                                  return_router_picks=True)
    row = np.zeros((1, GEOMETRY["max_seq_len"]), np.int32)
    row[0, :40] = ids
    want, probs = ref.logits_at(
        ref.weights_of(model), ref.hyper_of(model.config), row,
        np.arange(40)[None], with_router=True)
    want = np.asarray(want)[0]
    got = np.asarray(logits.value)[0]
    assert np.abs(got - want).max() / np.abs(want).max() <= TOLERANCE
    assert picks.shape == (8, 1, 40, 2)
    top = np.sort(np.argsort(np.asarray(probs), -1)[..., -2:], -1)
    assert (np.sort(np.asarray(picks), -1) == top).all()


def test_two_requests_of_unequal_length_share_steps(model, rec):
    """A chunked prompt and a whole one, decoding together: chunks and decode
    rows of different slots in one packed buffer."""
    eng = _engine(model, rec)
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((70, 5), (11, 9))]
    _run(eng)
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


def test_a_burst_of_short_prompts_is_prefilled_in_bounded_calls(
        model, rec, monkeypatch):
    """Three prompts of one length bucket admitted in one step: a whole-prompt
    call holds at most ``engine.WHOLE_PROMPT_ROWS`` rows (at 128 slots x 512
    tokens one call asked for 13.9 GiB beside the weights: PERF.md, PR 54),
    so with room for one of them the group goes in three calls, each row's
    state and cache written as from one."""
    from paddle_tpu.serving import engine as engine_mod
    monkeypatch.setattr(engine_mod, "WHOLE_PROMPT_ROWS", 32)
    eng = _engine(model, rec)
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=4))
            for n in (21, 19, 25)]
    _run(eng)
    assert eng.prefill_programs_asked == 3
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


# ------------------------------------------------ what the check would catch
def _forward_deviation(model, params=None, **statics):
    """The deviation from the reference of ``mod._forward`` on ``model``'s
    tree, or on a tree / static numbers with one particular wrong (a new
    trace each: the statics and the tree's structure key the jit)."""
    c = model.config
    ids = _prompt(48, 5)
    kw = dict(nh=c.num_attention_heads, nkv=c.num_key_value_heads,
              hd=c.head_dim, eps=float(c.rms_norm_eps),
              theta=float(c.rope_theta), rotary=c.rotary_dim, gdn=c.gdn,
              moe=c.routing, return_picks=False)
    kw.update(statics)
    got, _ = mod._forward(model.decode_params()[0] if params is None
                          else params, jnp.asarray([ids], jnp.int32), **kw)
    want = _reference_logits(model, ids, range(48))
    return float(np.abs(np.asarray(got)[0] - want).max()
                 / np.abs(want).max())


def _every_tree(params, edit):
    """``params`` with ``edit(tree)`` applied to the full layers' tree and to
    each place's linear tree."""
    out = edit(dict(params))
    out["linear_layers"] = tuple(edit(dict(t))
                                 for t in params["linear_layers"])
    return out


def _without(*names):
    return lambda tree: {k: v for k, v in tree.items() if k not in names}


def _ungated_query(tree):
    """``wq`` with a head's query columns only: no output gate."""
    if "wq" in tree:
        P, H, wide = tree["wq"].shape
        hd = tree["q_norm"].shape[-1]
        tree["wq"] = tree["wq"].reshape(P, H, -1, 2 * hd)[..., :hd].reshape(
            P, H, wide // 2)
    return tree


def _whole_projection_norm(tree):
    """The head's norm weights tiled over the heads: the same weights, the
    mean taken over the WHOLE projection."""
    if "wq" in tree:
        hd = tree["q_norm"].shape[-1]
        nkv = tree["wk"].shape[-1] // hd
        nh = tree["wo"].shape[1] // hd
        tree["q_norm"] = jnp.tile(tree["q_norm"], (1, nh))
        tree["k_norm"] = jnp.tile(tree["k_norm"], (1, nkv))
    return tree


def _tiled_key_heads(monkeypatch):
    """Value head ``h`` on key head ``h % Hk``, in the jnp forms' one
    statement of the grouping; a jit of its own, since no static number
    changes and jit's cache is by function (the right forward was traced at
    these very arguments)."""
    monkeypatch.setattr(gdr, "_to_value_heads", lambda x, heads: jnp.tile(
        x, (1, heads // x.shape[1], 1)))
    real = mod._forward.__wrapped__
    monkeypatch.setattr(mod, "_forward", jax.jit(
        lambda params, ids, **kw: real(params, ids, **kw),
        static_argnames=("nh", "nkv", "hd", "eps", "theta", "rotary", "gdn",
                         "moe", "return_picks")))


#: name: (edit of the tree, static numbers to replace as a function of the
#: configuration, a patch to apply first); None where there is none
FAULTS = {
    "w_for_1_plus_w": (_without("norm_plus_one"), None, None),
    "attention_gate_dropped": (
        lambda p: _every_tree(p, _ungated_query), None, None),
    "whole_head_rotated": (
        None, lambda c: dict(rotary=None), None),
    "whole_projection_norm": (
        lambda p: _every_tree(p, _whole_projection_norm), None, None),
    "value_head_on_key_head_h_mod_hk": (None, None, _tiled_key_heads),
    "beta_doubled": (
        None, lambda c: dict(gdn=c.gdn._replace(neg_eigval=True)), None),
    "shared_gate_dropped": (
        lambda p: _every_tree(p, _without("ws_sgate")), None, None),
    "weights_not_renormalised": (
        None, lambda c: dict(moe=(c.routing[0], False) + c.routing[2:]),
        None),
}


@pytest.fixture(scope="module")
def small():
    """One period (three linear layers and a full one): what a fault's own
    trace costs is its four layers'."""
    return _model(num_hidden_layers=4)


def test_the_right_forward_passes(small):
    assert _forward_deviation(small) <= TOLERANCE


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_wrong_forward_fails(fault, small, monkeypatch):
    """A layer with one particular of the model wrong reads far from the
    reference (one trace each; the right one reads under the tolerance)."""
    edit, statics, patch = FAULTS[fault]
    if patch is not None:
        patch(monkeypatch)
    params = small.decode_params()[0]
    got = _forward_deviation(
        small, params if edit is None else edit(params),
        **({} if statics is None else statics(small.config)))
    assert got > 30 * TOLERANCE, (fault, got)


# ------------------------------------------------------------ the two caches
def test_two_kinds_of_cache(model):
    c = model.config
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    assert (c.num_hidden_layers, c.num_linear_layers, c.num_kv_layers) == (
        8, 6, 2)
    # two pool layers: the full layers', a row a token
    assert eng.cache.pool.k.shape[0] == 2 == eng.cache.pool.v.shape[0]
    per_token = 2 * 2 * c.num_key_value_heads * c.head_dim * 4
    assert eng.cache.bytes_per_token() == per_token
    states, tails = eng.cache.state
    # the key width on the sublanes, every VALUE head's values on the lanes
    assert states.shape == (6, SLOTS, c.linear_key_head_dim,
                            c.linear_num_value_heads
                            * c.linear_value_head_dim)
    assert states.dtype == jnp.float32
    assert tails.shape == (6, SLOTS, c.linear_conv_kernel_dim - 1,
                           c.conv_channels)
    assert c.conv_channels == 2 * 2 * 8 + 4 * 16
    assert eng.cache.window is None


def test_a_bfloat16_state_is_refused(model):
    """The store's dtype is held where the program reads it: a state rounded
    to bfloat16 between steps moves the logits by less than a check on
    logits can see, so the program refuses it (as the Mamba layers' do)."""
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    ss, cs = eng.cache.state
    T = eng.step_rows[0]
    c = model.config
    z = jnp.zeros((SLOTS,), jnp.int32)
    with pytest.raises(TypeError, match="float32"):
        decode_mod._hybrid_span_forward(
            model.decode_params()[0], jnp.zeros((1, T, c.hidden_size)),
            None, None, (ss.astype(jnp.bfloat16), cs), None,
            seg=jnp.zeros((T,), jnp.int32), pos=jnp.zeros((T,), jnp.int32),
            qstart=z, qlen=z, kvlen=z, nh=c.num_attention_heads,
            nkv=c.num_key_value_heads, hd=c.head_dim,
            eps=float(c.rms_norm_eps), gdn=c.gdn, moe=c.routing)


def test_the_published_sizes():
    c = Qwen3NextConfig()
    assert (c.num_hidden_layers, c.num_kv_layers, c.num_linear_layers) == (
        48, 12, 36)
    assert (c.rotary_dim, c.conv_channels) == (64, 8192)
    assert c.gdn == (32, 128, 128, 4, False, "pallas", 16, 0)
    assert c.routing == (10, True, 1, 1, 0, 1.0)
    share = Qwen3NextConfig(num_experts=64, router_experts=512,
                            first_held_expert=448, num_hidden_layers=12)
    assert share.routing[4] == 448 and share.num_linear_layers == 9
    for bad in (dict(num_hidden_layers=10), dict(decoder_sparse_step=2),
                dict(linear_num_key_heads=12),
                dict(num_experts=64, router_experts=512,
                     first_held_expert=449)):
        with pytest.raises(ValueError):
            Qwen3NextConfig(**bad)


@pytest.mark.parametrize("switch", serving_support.OTHER_SWITCHES,
                         ids=lambda s: next(iter(s)))
def test_every_other_switch_raises_by_name(switch, model):
    geometry = {**GEOMETRY, **switch}
    with pytest.raises(ValueError, match="linear_layers") as e:
        serving_support.engine_as_given(model, **geometry)
    assert all(name in str(e.value) for name in switch)


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
        kernels[T] = tuple(name in text for name in (
            "gdn_chunk_scan", "gdn_recurrent_update",
            "ragged_paged_attention"))
    small, large = eng.step_rows
    assert kernels == {small: (False, True, True), large: (True, True, True)}


def test_served_over_http(model):
    """``serve(model)`` at its defaults: a chunked prompt through the gateway
    equals the model's own forward, and ``/metrics`` carries the state's
    bytes a slot beside the pool's a token and the routing's counters."""
    import urllib.request
    from paddle_tpu.serving.server import serve
    from test_olmoe_serving import _complete
    prompt = _prompt(45, seed=9)
    srv = serve(model, port=0, num_slots=SLOTS, max_seq_len=128,
                prefill_chunk=CHUNK)
    try:
        got = _complete(srv, prompt, 3)
        with urllib.request.urlopen(srv.url + "/metrics", timeout=60) as r:
            text = r.read().decode()
        cache = srv.gateway.engine.cache
    finally:
        srv.shutdown(drain=False, timeout=30)
    want = np.asarray(model.forward(np.asarray(
        [prompt + got], np.int32)).value)[0, 44:-1].argmax(-1)
    assert got == want.tolist()
    gauges = {ln.split()[0]: float(ln.split()[1])
              for ln in text.splitlines()
              if ln.startswith(("serving_state_bytes_per_slot ",
                                "serving_kv_bytes_per_token "))}
    assert gauges == {
        "serving_state_bytes_per_slot": cache.state_bytes_per_slot,
        "serving_kv_bytes_per_token": 2 * 2 * 2 * 16 * 4}
    assert "serving_moe_experts_touched_total" in text


# ------------------------------------------------------- the routed FFN alone
def test_the_shares_add_up():
    """Eight chips, each holding 2 of a 16-expert router's experts: their
    routed parts (each through ``moe_ffn`` with its held range) plus the
    shared expert and its gate ONCE equal the reference's uncut layer."""
    n_exp, top, rows, hid, wid = 16, 5, 24, 32, 16
    rng = np.random.default_rng(4)

    def rand(*s):
        return jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)

    g, router = rand(rows, hid), rand(hid, n_exp) * 2
    w = {"w_gate": rand(n_exp, hid, wid), "w_up": rand(n_exp, hid, wid),
         "w_down": rand(n_exp, wid, hid)}
    shared = {"ws_gate": rand(hid, 2 * wid), "ws_up": rand(hid, 2 * wid),
              "ws_down": rand(2 * wid, hid), "ws_sgate": rand(hid, 1)}
    hy = {"top_k": top, "norm_topk_prob": True, "first_held": 0}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_ffn(g, dict(router=router, **w, **shared),
                                 jnp.full((rows, top), -1), hy)
        got = jax.nn.sigmoid(g @ shared["ws_sgate"]) * ref._swiglu(
            g, shared["ws_gate"], shared["ws_up"], shared["ws_down"])
        pairs = 0
        for first in range(0, n_exp, 2):
            part, stats = moe_mod.moe_ffn(
                g, router, *(w[n][first:first + 2] for n in ref.EXPERTS),
                top_k=top, renormalize=True, first_held=first)
            got, pairs = got + part, pairs + int(stats[0])
    assert pairs == rows * top              # every pick lands on one share
    assert np.abs(np.asarray(got - want)).max() \
        <= TOLERANCE * np.abs(np.asarray(want)).max()
