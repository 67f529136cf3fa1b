"""Phi-4-mini-flash-reasoning (SambaY) through the serving engine (ISSUE 37):
Mamba layers and window layers whose caches are constant a slot, ONE paged KV
layer that eight attention layers read, a cross-decoder that caches nothing
and runs at one row a slot.

The engine against the plain reference ON LOGITS
(``benchmark/reference_phi4_flash.py``: the selective scan token by token, a
masked softmax, float32): every token the engine generates is produced from
logits that equal the reference's full forward at that position, for
whole-prompt prefill then decode, for a prompt through three chunks with a
window smaller than a chunk and smaller than the prompt, for two requests of
unequal length in one step, in a slot a longer sequence used before, after
preemption by recompute and after a fence that raised. Tolerance 1e-4 of the
largest logit: float32 on both sides (conftest sets matmul precision
``highest``). The last tests before the stores' show a dropped window, a
``lambda`` of 0, a memory taken after the gate (in the model's own forward,
one program each), a convolution tail or a window ring lost at a chunk
boundary and a cross layer that reads rows nobody wrote (through the engine)
each failing. The module's engines share one set of compiled programs
(``JIT``), with the logits' recorder inside them.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models.phi4_flash import Phi4FlashConfig, phi4_flash_tiny
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import drain as _run, token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_phi4_flash as ref  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
CHUNK = 32
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=128, decode_chunk=1,
                prefill_chunk=CHUNK, prefix_block_size=8)


def _model(kernel="jnp", seed=7, **kw):
    return serving_support.model("phi4_flash", seed=seed,
                                 decode_attention=kernel, **kw)


@pytest.fixture(scope="module")
def model():
    return _model()


#: the programs of the module's one jnp model, compiled once: every test's
#: engine shares them (and the recorder inside them, ``_recorder``)
JIT = {}


def _reference_logits(model, ids, at, config=None):
    return serving_support.reference_logits(
        ref, model, ids, at, GEOMETRY["max_seq_len"], config)


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
    # name: (prompt length, new tokens); the window is 16, a chunk 32
    "whole_prompt_then_decode": (21, 20),
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


def test_kernels_interpreted(rec):
    """The three Pallas kernels in interpret mode through the engine, in ONE
    program (a second costs another 25 s of tracing): two prompts of two
    chunks (the scan from a zero state and from the store, the windowed walk
    over the ring), the second's chunks beside the first's decode row (the
    in-place update, a one-token span inside the window)."""
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
    """The model's own whole-sequence forward (the cross-decoder on every
    token; with ``pallas`` the chunk scan from a zero state, as whole-prompt
    prefill runs it) against the reference at every position."""
    model = _model(kernel)
    ids = _prompt(40, 3)
    got = np.asarray(model.forward(np.asarray([ids], np.int32)).value)[0]
    want = _reference_logits(model, ids, range(40))
    assert np.abs(got - want).max() / np.abs(want).max() <= TOLERANCE


def test_two_requests_of_unequal_length_share_steps(model, rec):
    """A chunked prompt and a whole one, decoding together: chunks and decode
    rows of different slots in one packed buffer, then one row a slot."""
    eng = _engine(model, rec)
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((70, 5), (11, 9))]
    _run(eng)
    for seq in seqs:
        assert seq.done
        assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


@pytest.mark.parametrize("second", [40, 9], ids=["chunked", "whole"])
def test_a_reused_slot_holds_nothing_stale(second, model, rec):
    """No program zeroes a slot: the second, SHORTER sequence in slot 0
    reads the logits a fresh engine gives (no stale state, tail, window ring
    or memory), because its first span starts at 0."""
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
    assert eng.stats["preemptions"] == 1 and eng.stats["restores"] == 1
    assert eng.stats["state_restarts_preempt"] == 1
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE


class _Broken:
    def __init__(self, real):
        self.real = real

    def __array__(self, *a, **kw):
        raise RuntimeError("device lost")


def test_a_fence_that_raises_restarts_from_position_zero(model, rec):
    """The dropped programs applied their tokens to the states and the
    rings: every sequence they carried is recomputed from position 0."""
    eng = _engine(model, rec)
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
def _forward_deviation(model, config=None):
    """The model's whole-sequence forward against the reference that reads
    ``config`` (the model's own unless given), over a 60-token sequence."""
    ids = _prompt(60, 5)
    got = np.asarray(model.forward(np.asarray([ids], np.int32)).value)[0]
    want = _reference_logits(model, ids, range(60), config)
    return float(np.abs(got - want).max() / np.abs(want).max())


def _lambda_zero(real):
    def combine(o, lw, eps, dtype):
        o = o.reshape(o.shape[:-2] + (-1, 2, o.shape[-1]))
        o = o.at[..., 1, :].set(0.0)    # o2 never subtracted
        return real(o.reshape(o.shape[:-3] + (-1, o.shape[-1])), lw, eps,
                    dtype)
    return combine


def _memory_after_the_gate(real):
    def mixer(hn, lw, *, conv, scan):
        out, (tail, st, y) = real(hn, lw, conv=conv, scan=scan)
        z = jnp.einsum("bsh,hc->bsc", hn, lw["ssm_in"])[..., y.shape[-1]:]
        return out, (tail, st, y * jax.nn.silu(z))
    return mixer


FORWARD_WRONG = {"lambda_zero": ("_diff_combine", _lambda_zero),
                 "memory_after_the_gate": ("_mamba_mixer",
                                           _memory_after_the_gate)}


@pytest.mark.parametrize("variant", sorted(FORWARD_WRONG) + ["window_dropped"])
def test_wrong_forward_fails(variant, monkeypatch):
    """In the model's own forward (one program, the layer bodies the step
    programs run): each fault moves the logits far past the tolerance."""
    if variant == "window_dropped":     # every window layer attends from 0
        wrong = _model(sliding_window=1 << 20)
        assert _forward_deviation(wrong, phi4_flash_tiny()) > 30 * TOLERANCE
        return
    name, make = FORWARD_WRONG[variant]
    monkeypatch.setattr(decode_mod, name, make(getattr(decode_mod, name)))
    assert _forward_deviation(_model(seed=11)) > 30 * TOLERANCE


@pytest.mark.parametrize("variant", ["lost_conv_tail", "lost_window_ring"])
def test_a_store_lost_at_a_chunk_boundary_fails(variant, model, rec):
    eng = _engine(model, rec)
    seq = eng.submit(GenerationRequest(_prompt(75), max_new_tokens=4))

    def between():
        ss, cs, wk, wv = eng.cache.store
        if variant == "lost_conv_tail":
            eng.cache.store = (ss, jnp.zeros_like(cs), wk, wv)
        else:
            eng.cache.store = (ss, cs, jnp.zeros_like(wk), wv)

    _run(eng, between)
    assert _deviation(model, seq, rec.rows[seq.request_id]) > 10 * TOLERANCE


def test_a_cross_layer_reading_unwritten_rows_fails(model, rec, monkeypatch):
    """The cross layers read the middle layer's pool layer AFTER it wrote the
    step's rows: with the write lost they read rows nobody wrote."""
    real = decode_mod._kv_write
    monkeypatch.setattr(decode_mod, "_kv_write",
                        lambda pool, at, x: real(pool, at, 0 * x))
    eng = _engine(model, rec, jit_cache={})
    seq = eng.submit(GenerationRequest(_prompt(40), max_new_tokens=3))
    _run(eng)
    assert _deviation(model, seq, rec.rows[seq.request_id]) > 30 * TOLERANCE


# --------------------------------------------------------- the three stores
def test_three_kinds_of_cache(model):
    c = model.config
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    bs = GEOMETRY["prefix_block_size"]
    assert c.num_hidden_layers == 8 and c.num_kv_layers == 1
    # ONE pool layer: the middle full layer's, a row a token
    assert eng.cache.pool.k.shape[0] == 1 == eng.cache.pool.v.shape[0]
    per_token = 2 * c.num_key_value_heads * c.head_dim * 4
    assert eng.cache.bytes_per_token() == per_token
    states, tails = eng.cache.state
    assert states.shape == (3, SLOTS, c.mamba_d_state, c.d_inner)
    assert states.dtype == jnp.float32
    assert tails.shape == (3, SLOTS, c.mamba_d_conv - 1, c.d_inner)
    # the window layers' rings: window + a chunk + a block, in blocks;
    # constant a slot whatever max_seq_len is
    ring = -(-(c.sliding_window + CHUNK + bs - 1) // bs)
    wk, wv = eng.cache.window
    assert wk.shape == wv.shape == (2, SLOTS, ring, bs,
                                    c.num_key_value_heads * c.head_dim)
    long = serving_support.engine_as_given(
        model, **{**GEOMETRY, "max_seq_len": 1024})
    assert long.cache.window_bytes_per_slot == eng.cache.window_bytes_per_slot
    assert eng.cache.window_bytes_per_slot == 2 * wk[:, 0].size * 4
    assert eng.cache.state_bytes_per_slot == (states[:, 0].size
                                              + tails[:, 0].size) * 4
    occ = eng.cache.occupancy_bytes()
    assert occ["capacity_window"] == SLOTS * eng.cache.window_bytes_per_slot
    assert occ["capacity_state"] == SLOTS * eng.cache.state_bytes_per_slot
    assert occ["per_token"] == per_token
    # a slot's write takes each store's own layout and nothing else: a state
    # handed over as [layers, channels, d_state] is a mistake, not a reshape
    held = (states[:, 0], tails[:, 0], wk[:, 0].reshape(2, ring * bs, -1),
            wv[:, 0].reshape(2, ring * bs, -1))
    eng.cache.write_state(1, *held)
    with pytest.raises(ValueError, match="does not take"):
        eng.cache.write_state(1, jnp.swapaxes(held[0], 1, 2), *held[1:])


def test_metrics_tell_the_three_apart(model):
    from paddle_tpu.serving.server import serve
    server = serve(model, port=0, **{k: v for k, v in GEOMETRY.items()
                                     if k != "decode_chunk"})
    try:
        import urllib.request
        text = urllib.request.urlopen(server.url + "/metrics").read().decode()
    finally:
        server.shutdown()
    cache = server.gateway.engine.cache if hasattr(server, "gateway") \
        else None
    for name in ("serving_window_bytes_per_slot",
                 "serving_state_bytes_per_slot", "serving_kv_bytes_per_token",
                 "serving_state_restarts_total"):
        assert name in text, name
    if cache is not None:
        assert f"serving_window_bytes_per_slot {cache.window_bytes_per_slot}" \
            in text.replace(".0", "")


@pytest.mark.parametrize("switch", serving_support.OTHER_SWITCHES,
                         ids=lambda s: next(iter(s)))
def test_every_other_switch_raises_by_name(switch, model):
    geometry = {**GEOMETRY, **switch}
    with pytest.raises(ValueError, match="self_layers") as e:
        serving_support.engine_as_given(model, **geometry)
    assert all(name in str(e.value) for name in switch)


def test_dispatch_args_split_the_kernel_by_layer_kind(model):
    eng = serving_support.engine_as_given(
        model, **{**GEOMETRY, "max_seq_len": 1024})
    qstart = np.array([0, 1, 0], np.int32)
    qlen = np.array([1, 32, 0], np.int32)
    kvlen = np.array([900, 800, 0], np.int32)
    args = eng._dispatch_args(qstart, qlen, kvlen, eng._token_budget, 1, 1,
                              32)
    assert args["cross_rows"] == SLOTS
    assert args["state_rows"] == 2 and args["scan_spans"] == 1
    # the full layer and the cross layers' one-token rows need both caches
    # whole; a window layer the keys its queries may see: the last
    # ``window`` of a decode row, ``window - 1`` more than a chunk's tokens
    window = model.config.sliding_window
    assert args["live_steps"] == -(-900 // 8) + 800 // 8
    assert args["kv_tokens"] == 900 + 800
    assert args["window_kv_tokens"] == window + (32 + window - 1)


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
    # one call in the scanned pair's body, one in the middle layer
    assert kernels == {small: (0, 2), large: (2, 2)}


def test_config_refuses_what_the_forward_cannot_run():
    with pytest.raises(ValueError, match="multiple of 4"):
        Phi4FlashConfig(num_hidden_layers=6)
    with pytest.raises(ValueError, match="mb_per_layer"):
        Phi4FlashConfig(mb_per_layer=3)
    c = Phi4FlashConfig()
    assert (c.d_inner, c.mamba_dt_rank, c.head_dim) == (5120, 160, 64)
    assert (c.num_ssm_layers, c.num_window_layers, c.num_kv_layers) == \
        (9, 8, 1)
