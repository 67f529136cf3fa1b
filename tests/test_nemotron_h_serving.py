"""NVIDIA-Nemotron-3-Nano (Nemotron-H) through the serving engine (ISSUE 47):
blocks that are ONE mixer each, a Mamba-2 state by slot beside a KV pool of
the attention blocks only, two-matrix experts under a sigmoid router.

The engine against the plain reference ON LOGITS
(``benchmark/reference_nemotron_h.py``: the recurrence token by token, a
masked softmax, every expert over every row, float32): every token the
engine generates is produced from logits that equal the reference's full
forward at that position, on the pattern ``MEM*EME`` (every kind of unit),
for whole-prompt prefill then decode, for a prompt through three chunks (a
chunk boundary inside the prompt), for two requests of unequal length in one
step, in a slot a longer sequence used before, after preemption by recompute.
Tolerance 1e-4 of the largest logit: float32 on both sides (conftest sets
matmul precision ``highest``). Then what a wrong block would read, the stores'
geometry and counts, the eight shares of a routed FFN adding up to the uncut
block, the two expert bodies of ``moe_ffn``, and every switch whose program
was not taught the blocks raising. The module's engines share one set of
compiled programs (``JIT``), with the logits' recorder inside them.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models.nemotron_h import PUBLISHED_PATTERN, NemotronHConfig
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import drain as _run, token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_nemotron_h as ref  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
CHUNK = 32
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=128, decode_chunk=1,
                prefill_chunk=CHUNK, prefix_block_size=8)


def _model(kernel="jnp", seed=7, **kw):
    return serving_support.model("nemotron_h", seed=seed,
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
    # three routed FFNs a program call; 4 of the router's 8 experts held
    assert eng.stats["moe_layer_calls"] % 3 == 0
    assert 0 < eng.stats["moe_pairs"] < eng.stats["moe_picks"]


def test_kernels_interpreted(rec):
    """The Pallas kernels in interpret mode through the engine, in ONE
    program: two prompts of two chunks (the dual-form scan from a zero state
    and from the store), the second's chunks beside the first's decode row
    (the in-place update), the ragged kernel at a query group of 2."""
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
    """The model's own whole-sequence forward (with ``pallas`` the chunk
    scan from a zero state, as whole-prompt prefill runs it) against the
    reference at every position, and its picks against the reference's."""
    model = _model(kernel)
    ids = _prompt(40, 3)
    logits, picks = model.forward(np.asarray([ids], np.int32),
                                  return_router_picks=True)
    row = np.zeros((1, GEOMETRY["max_seq_len"]), np.int32)
    row[0, :40] = ids
    want, scores = ref.logits_at(
        ref.weights_of(model), ref.hyper_of(model.config), row,
        np.arange(40)[None], with_router=True)
    want = np.asarray(want)[0]
    got = np.asarray(logits.value)[0]
    assert np.abs(got - want).max() / np.abs(want).max() <= TOLERANCE
    top = np.sort(np.argsort(np.asarray(scores), -1)[..., -2:], -1)
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


@pytest.mark.parametrize("second", [40, 9], ids=["chunked", "whole"])
def test_a_reused_slot_holds_nothing_stale(second, model, rec):
    """No program zeroes a slot: the second, SHORTER sequence in slot 0
    reads the logits a fresh engine gives (no stale state or tail), because
    its first span starts at 0."""
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


# ------------------------------------------------ what the check would catch
def _forward_deviation(model):
    ids = _prompt(60, 5)
    got = np.asarray(model.forward(np.asarray([ids], np.int32)).value)[0]
    want = _reference_logits(model, ids, range(60))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _no_skip(hn, lw, **kw):
    return _REAL_MIXER(hn, dict(lw, ssd_D=jnp.zeros_like(lw["ssd_D"])), **kw)


_REAL_MIXER = decode_mod._ssd_mixer
WRONG = {
    "skip_dropped": lambda mp: mp.setattr(decode_mod, "_ssd_mixer", _no_skip),
    "relu_not_squared": lambda mp: [mp.setattr(
        m, "relu2", lambda x: jnp.maximum(x, 0)) for m in (decode_mod,
                                                           moe_mod)],
    "attention_blocks_skipped": lambda mp: mp.setattr(
        decode_mod, "_attention", lambda q, k, v, causal: jnp.zeros_like(q)),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_wrong_forward_fails(variant, monkeypatch):
    """A block with one mechanism wrong reads far from the reference (one
    program each; the right one reads under the tolerance above)."""
    # (a model of its own: ``_forward`` is jitted on the functions it finds)
    from paddle_tpu.models import nemotron_h as mod
    WRONG[variant](monkeypatch)
    monkeypatch.setattr(mod, "_forward", jax.jit(
        mod._forward.__wrapped__, static_argnames=(
            "nh", "nkv", "hd", "eps", "ssd", "moe", "return_picks")))
    assert _forward_deviation(_model()) > 30 * TOLERANCE


# ------------------------------------------------------------ the two caches
def test_two_kinds_of_cache(model):
    c = model.config
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    assert (c.num_hidden_layers, c.num_units, c.num_kv_layers) == (7, 3, 1)
    assert c.unit_attention == [-1, 0, -1]
    # ONE pool layer: the attention block's, a row a token
    assert eng.cache.pool.k.shape[0] == 1 == eng.cache.pool.v.shape[0]
    per_token = 2 * c.num_key_value_heads * c.head_dim * 4
    assert eng.cache.bytes_per_token() == per_token
    states, tails = eng.cache.state
    # the state size on the sublanes, a group's channels on the lanes
    # (``kernels.ssd``'s layout)
    assert states.shape == (3, SLOTS, c.n_groups, c.ssm_state_size,
                            c.mamba_num_heads // c.n_groups
                            * c.mamba_head_dim)
    assert states.dtype == jnp.float32
    assert tails.shape == (3, SLOTS, c.conv_kernel - 1, c.conv_channels)
    assert eng.cache.state_bytes_per_slot == (states[:, 0].size
                                              + tails[:, 0].size) * 4
    assert eng.cache.window is None
    # the dispatch span: the Mamba-2 kernels' work over the step's blocks
    qstart = np.array([0, 1, 0], np.int32)
    qlen = np.array([1, 32, 0], np.int32)
    kvlen = np.array([90, 64, 0], np.int32)
    args = eng._dispatch_args(qstart, qlen, kvlen, eng._token_budget, 1, 1,
                              32)
    assert args["state_rows"] == 2 and args["scan_spans"] == 1
    assert (args["ssd_update_rows"], args["ssd_scan_tokens"],
            args["ssd_scan_spans"]) == (3 * 1, 3 * 32, 3 * 1)
    assert args["kv_tokens"] == 90 + 64


def test_the_published_sizes():
    c = NemotronHConfig()
    assert len(PUBLISHED_PATTERN) == 52 == c.num_hidden_layers
    assert (c.num_units, c.num_kv_layers) == (23, 6)
    # the attention blocks follow blocks 4, 11, 18, 25, 32, 41
    assert [i for i, k in enumerate(PUBLISHED_PATTERN) if k == "*"] == [
        5, 12, 19, 26, 33, 42]
    assert [u for u, a in enumerate(c.unit_attention) if a >= 0] == [
        2, 5, 8, 11, 14, 18]
    assert (c.d_inner, c.conv_channels) == (4096, 6144)
    assert c.ssd[:5] == (64, 64, 8, 128, 4) and c.routing == (
        6, True, 1, 1, 0, 2.5)
    share = NemotronHConfig(n_routed_experts=16, router_experts=128,
                            first_held_expert=112)
    assert share.routing[4] == 112
    for bad in (dict(hybrid_override_pattern="MEM*EMM", num_hidden_layers=7),
                dict(num_hidden_layers=51), dict(n_group=2),
                dict(n_routed_experts=16, router_experts=128,
                     first_held_expert=113)):
        with pytest.raises(ValueError):
            NemotronHConfig(**bad)


@pytest.mark.parametrize("switch", serving_support.OTHER_SWITCHES,
                         ids=lambda s: next(iter(s)))
def test_every_other_switch_raises_by_name(switch, model):
    geometry = {**GEOMETRY, **switch}
    with pytest.raises(ValueError, match="ssd_layers") as e:
        serving_support.engine_as_given(model, **geometry)
    assert all(name in str(e.value) for name in switch)


def test_the_decode_only_program_has_no_chunk_scan():
    """The plan gives the small program one-token spans only, so it launches
    the in-place update and not the chunked scan; the attention block is one
    conditional in the one scanned unit (traced, never run)."""
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
        kernels[T] = (text.count("ssd_chunk_scan"),
                      text.count("ssd_recurrent_update"),
                      text.count("ragged_paged_attention"))
    small, large = eng.step_rows
    assert kernels == {small: (0, 1, 1), large: (1, 1, 1)}


def test_served_over_http_and_metrics_tell_the_state_from_the_pool(model):
    """``serve(model)`` at its defaults: a chunked prompt through the gateway
    equals the direct engine's stream, and ``/metrics`` carries the state's
    bytes a slot beside the pool's a token."""
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
        "serving_kv_bytes_per_token": 2 * 2 * 16 * 4}
    assert "serving_state_restarts_total" in text


# ------------------------------------------------------- the routed FFN alone
def _ffn_inputs(n_exp, wid=16, rows=24, hid=32):
    rng = np.random.default_rng(4)

    def rand(*s):
        return jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)

    return (rand(rows, hid), rand(hid, n_exp) * 2, rand(n_exp) * 0.1,
            {"w_gate": rand(n_exp, hid, wid), "w_up": rand(n_exp, hid, wid),
             "w_down": rand(n_exp, wid, hid)},
            {"ws_up": rand(hid, 2 * wid), "ws_down": rand(2 * wid, hid)})


def test_the_shares_add_up():
    """Eight chips, each holding 2 of a 16-expert sigmoid router's experts:
    their routed parts (each through ``moe_ffn`` with its held range, the
    bias and no gate matrix) plus the shared expert once equal the
    reference's uncut block."""
    n_exp, top = 16, 6
    g, router, bias, w, shared = _ffn_inputs(n_exp)
    hy = {"top_k": top, "norm_topk_prob": True, "first_held": 0,
          "routed_scale": 2.5}
    up_t = jnp.swapaxes(w["w_up"], 1, 2)    # stored by output unit
    whole = dict(router=router, router_bias=bias, w_up=up_t,
                 w_down=w["w_down"], **shared)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_ffn(g, whole, jnp.full((g.shape[0], top), -1),
                                 hy)
        got = ref._mlp(g, shared["ws_up"], shared["ws_down"])
        pairs = 0
        for first in range(0, n_exp, 2):
            part, stats = moe_mod.moe_ffn(
                g, router, None, up_t[first:first + 2],
                w["w_down"][first:first + 2], top_k=top, renormalize=True,
                first_held=first, scale=2.5, router_bias=bias)
            got, pairs = got + part, pairs + int(stats[0])
    assert pairs == g.shape[0] * top        # every pick lands on one share
    assert np.abs(np.asarray(got - want)).max() \
        <= TOLERANCE * np.abs(np.asarray(want)).max()


def _parent_moe_ffn(h, router, w_gate, w_up, w_down, *, top_k, **routing):
    """``moe_ffn``'s body as the parent commit had it (three matrices)."""
    lead, h2, live = moe_mod._prep(h, None)
    rows, hid = h2.shape
    w, _, idx, counts, _ = moe_mod._route(h2, router, top_k, live, False,
                                          w_gate.shape[0], **routing)
    pairs = rows * top_k
    slots = -(-pairs // moe_mod.PAIR_TILE) * moe_mod.PAIR_TILE
    order = jnp.pad(jnp.argsort(idx.reshape(-1), stable=True),
                    (0, slots - pairs))
    xs = jnp.take(h2, order // top_k, axis=0)
    gm = moe_mod._grouped_matmul
    y = gm((jax.nn.silu(gm(xs, w_gate, counts)) * gm(xs, w_up, counts)
            ).astype(h2.dtype), w_down, counts)
    y = jnp.take(y, jnp.argsort(order[:pairs]), axis=0).reshape(
        rows, top_k, hid)
    y = jnp.where((idx < counts.shape[0])[:, :, None],
                  y.astype(jnp.float32), 0.0)
    return jnp.sum(y * w[:, :, None], axis=1).astype(h.dtype).reshape(
        lead + (hid,))


def test_two_expert_bodies():
    """A tree without ``w_gate`` runs ``relu(x W_up)^2 W_down``, one with it
    the SwiGLU; each equals ``moe_ffn_reference``, and the three-matrix
    path's output is the parent's to the bit."""
    g, router, bias, w, _ = _ffn_inputs(8)
    kw = dict(top_k=2, router_bias=bias, scale=2.5, first_held=0)
    outs = {}
    for name, gate, up in (("two", None, jnp.swapaxes(w["w_up"], 1, 2)),
                           ("three", w["w_gate"], w["w_up"])):
        got, stats = moe_mod.moe_ffn(g, router, gate, up, w["w_down"], **kw)
        want, want_stats = moe_mod.moe_ffn_reference(
            g, router, gate, up, w["w_down"], **kw)
        assert np.abs(np.asarray(got - want)).max() \
            <= TOLERANCE * np.abs(np.asarray(want)).max()
        assert np.asarray(stats).tolist() == np.asarray(want_stats).tolist()
        outs[name] = np.asarray(got)
    assert np.abs(outs["two"] - outs["three"]).max() > 0.01
    np.testing.assert_array_equal(outs["three"], np.asarray(_parent_moe_ffn(
        g, router, w["w_gate"], w["w_up"], w["w_down"], **kw)))
