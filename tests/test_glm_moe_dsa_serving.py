"""GLM-5.2 through the serving engine (ISSUE 43): served logits against the
plain float32 reference (whole-prompt prefill then decode; three chunks
through the unified step then decode; the kernels' path), two sequences of
unequal length in one step, a slot reused after a longer sequence, the
``indexer_types`` list honoured, the two caches' bytes, the shares of a
16-way expert-parallel layer adding up to the uncut layer, every switch whose
program was not taught the layer raising. (A tree with no indexer running
the programs PR 42's tree ran is ``tests/test_dsa_kernels.py``'s: this
module's programs record their logits.)"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                           glm_moe_dsa_tiny,
                                           published_indexer_types)
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

import serving_support
from serving_support import token_list as _prompt

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))
import reference_glm_moe_dsa as ref  # noqa: E402

SLOTS = 2
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=96, decode_chunk=1,
                prefill_chunk=32)
TOLERANCE = 1e-4
#: every engine of this module shares its programs (a model's weights are
#: arguments, not constants): one compilation a (path, packed size)
JIT = {}
RECORDS = []


@pytest.fixture(scope="module", autouse=True)
def recorded_logits():
    """Every program computes its logits in ``decode._head_logits``: record
    each call's rows, for the whole module (the programs are traced once)."""
    real = decode_mod._head_logits

    def recording(last_h, head):
        logits = real(last_h, head)
        jax.debug.callback(lambda x: RECORDS.append(np.asarray(x)), logits,
                           ordered=True)
        return logits

    decode_mod._head_logits = recording
    yield
    decode_mod._head_logits = real


@functools.lru_cache(maxsize=None)
def _model(attention="jnp", **config):
    """One model a path for the whole module (building one compiles), and
    the module's own: what it hangs on its models is traced with the
    recorder inside."""
    return serving_support.fresh_model("glm_moe_dsa", seed=11,
                                       decode_attention=attention, **config)


def _engine(model, jit_cache=JIT):
    """The shared helper at this file's geometry, on the module's recorded
    programs (``JIT``), which nobody else may run."""
    return serving_support.watch_prefill_programs(
        serving_support.engine_as_given(model, jit_cache=jit_cache,
                                        **GEOMETRY))


def _serve_one(model, prompt, n_new, eng=None):
    """(engine, tokens, the logits row each token was sampled from)."""
    eng = eng or _engine(model)
    rows = []

    def on_token(seq, _tok):
        jax.effects_barrier()
        if len(seq.tokens) == 1 \
                and seq.work_len <= GEOMETRY["prefill_chunk"]:
            rows.append([r for r in RECORDS if r.shape[0] != SLOTS][-1][0])
            return
        steps = [r for r in RECORDS if r.shape[0] == SLOTS]
        rows.append(steps[-2 if eng._inflight is not None else -1][seq.slot])

    eng.on_token = on_token
    seq = eng.submit(GenerationRequest(prompt, max_new_tokens=n_new))
    while eng.has_work():
        eng.step()
    eng.on_token = None
    assert seq.done and len(seq.tokens) == n_new == len(rows)
    return eng, list(seq.tokens), np.stack(rows)


def _reference_logits(model, prompt, tokens):
    # (one width for every call: the reference compiles its layers once)
    ids = np.zeros((1, GEOMETRY["max_seq_len"]), np.int32)
    ids[0, :len(prompt) + len(tokens)] = prompt + tokens
    at = np.asarray([[len(prompt) - 1 + k for k in range(len(tokens))]])
    return np.asarray(ref.logits_at(ref.weights_of(model),
                                    ref.hyper_of(model.config), ids, at))[0]


CASES = {
    # name: (prompt length, new tokens, attention path); index_topk is 8
    "whole_prompt_then_decode": (21, 5, "jnp"),
    "three_chunks_then_decode": (75, 4, "jnp"),
    "two_chunks_then_decode_kernels": (40, 3, "pallas"),
    # 16 index heads: a decode row's index scores on its own wide rows
    "two_chunks_then_decode_kernels_16_index_heads": (40, 3, "pallas"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_equal_reference(case):
    n_prompt, n_new, attention = CASES[case]
    prompt = _prompt(n_prompt)
    if case.endswith("16_index_heads"):
        # (other shapes than the module's programs: a cache of its own)
        model = _model(attention, index_n_heads=16)
        eng = _engine(model, jit_cache={})
        assert eng._dispatch_args([0], [1], [41], 8, 1, 1, 0)[
            "index_one_token_rows"] == 2
    else:
        model, eng = _model(attention), None
    eng, tokens, rows = _serve_one(model, prompt, n_new, eng)
    want = _reference_logits(model, prompt, tokens)
    assert np.abs(rows - want).max() / np.abs(want).max() <= TOLERANCE
    if n_prompt > GEOMETRY["prefill_chunk"]:
        assert eng.prefill_programs_asked == 0      # chunks only
        assert eng.stats["prefill_chunks"] == -(-n_prompt // 32)
    c = model.config
    assert eng.stats["moe_layer_calls"] % (
        c.num_hidden_layers - c.first_k_dense_replace) == 0
    assert 0 < eng.stats["moe_pairs"] < eng.stats["moe_picks"]


def _greedy_is_the_references(model, prompt, tokens):
    """Every served token is the float32 reference's argmax at its place,
    teacher-forced on the served sequence."""
    want = _reference_logits(model, prompt, tokens)
    return (want.argmax(-1) == np.asarray(tokens)).all()


def test_two_sequences_of_unequal_length_in_one_step():
    model = _model()
    eng = _engine(model)
    prompts = [_prompt(19, 1), _prompt(70, 2)]
    seqs = [eng.submit(GenerationRequest(p, max_new_tokens=6))
            for p in prompts]
    while eng.has_work():
        eng.step()
    for p, s in zip(prompts, seqs):
        assert _greedy_is_the_references(model, p, list(s.tokens))


def test_a_slot_reused_after_a_longer_sequence():
    """The second request takes the slot and the blocks the first one left,
    index keys and all: nothing stale is scored or attended."""
    model = _model()
    eng = _engine(model)
    _, first, _ = _serve_one(model, _prompt(75, 3), 3, eng)
    free = eng.cache.num_free
    _, tokens, rows = _serve_one(model, _prompt(21, 4), 5, eng)
    assert free == SLOTS and eng.cache.num_free == SLOTS
    want = _reference_logits(model, _prompt(21, 4), tokens)
    assert np.abs(rows - want).max() / np.abs(want).max() <= TOLERANCE


def test_indexer_types_are_honoured():
    """A ``shared`` layer attends over the set of the ``full`` layer before
    it; a model whose every layer selects for itself is another model; and
    the model's own ``forward`` is the reference's."""
    model = _model()
    ids = np.zeros((1, GEOMETRY["max_seq_len"]), np.int32)
    ids[0, :30] = _prompt(30, 5)
    weights, hy = ref.weights_of(model), ref.hyper_of(model.config)
    x, sets = ref.hidden_states(weights, hy, ids, with_sets=True)
    sets = np.asarray(sets)[:, 0, :30, :30]         # [L, S, S]
    assert hy["indexer_types"] == ("full", "shared", "shared", "full",
                                   "shared")
    assert (sets[1] == sets[0]).all() and (sets[2] == sets[0]).all()
    assert (sets[4] == sets[3]).all() and (sets[3] != sets[0]).any()
    assert (sets.sum(-1) == np.minimum(np.arange(30) + 1, 8)).all()
    # an indexer of its own in every expert layer, every other weight kept
    rng = np.random.RandomState(0)
    every = dict(weights, expert={
        n: (jnp.asarray(rng.randn(4, *a.shape[1:]) * 0.02, a.dtype)
            if n.startswith("idx_w") else
            jnp.broadcast_to(a[:1], (4,) + a.shape[1:])
            if n.startswith("idx_") else a)
        for n, a in weights["expert"].items()})
    x_every = ref.hidden_states(every, dict(hy, indexer_types=("full",) * 5),
                                ids)
    assert np.abs(np.asarray(x_every - x)[0, :30]).max() \
        > 100 * TOLERANCE * np.abs(np.asarray(x)).max()
    at = np.arange(30)[None]
    want = np.asarray(ref.logits_at(weights, hy, ids, at))
    got = np.asarray(model.forward(ids).value)[:, :30]
    assert np.abs(got - want).max() / np.abs(want).max() <= TOLERANCE


def test_published_list_and_places():
    kinds = published_indexer_types(78)
    assert "".join(k[0] for k in kinds[:15]) == "fffsssfsssfsssf"
    assert [i for i, k in enumerate(kinds) if k == "full"][3:6] == [6, 10, 14]
    assert kinds.count("full") == 3 + 18 and kinds[74] == "full"
    c = glm_moe_dsa_tiny()
    (dl, ds), (el, es) = c.indexer_places()
    assert dl.tolist() == [0] and ds.tolist() == [0]
    assert el.tolist() == [-1, -1, 1, -1] and es.tolist() == [0, 0, 0, 0]
    assert c.dsa.layers == 2 and c.dsa.topk == 8
    with pytest.raises(ValueError):
        glm_moe_dsa_tiny(indexer_types=["shared"] + ["full"] * 4)
    with pytest.raises(ValueError):
        glm_moe_dsa_tiny(n_group=2)
    full = GlmMoeDsaConfig()
    assert (full.head_dim, full.dsa.layers, full.routing[5]) == (256, 21, 2.5)


def test_two_caches_under_one_table():
    model = _model()
    eng = _engine(model)
    pool = eng.cache.pool
    c = model.config
    assert pool.k.shape[0] == 5 and pool.k.shape[-1] == 128
    assert pool.v.shape == (2,) + pool.k.shape[1:3] + (c.index_head_dim,)
    assert eng.cache.index_bytes_per_token == 2 * 16 * 4
    assert eng.cache.bytes_per_token() == 5 * 128 * 4
    assert pool.block_nbytes == eng.cache.block_size * (5 * 128 + 2 * 16) * 4
    args = eng._dispatch_args([0, 1], [1, 20], [30, 52], 24, 1, 1, 20)
    # one decode row at position 29, a chunk over positions 32..51
    assert args["index_query_rows"] == 2 * 21
    assert args["index_key_rows"] == 2 * args["attn_pairs"]
    assert args["selected_rows"] == 5 * 21 * 8
    assert args["attended_rows"] == 5 * args["attn_pairs"]


@functools.lru_cache(maxsize=None)
def _wide_indexer_engine():
    """An engine over an indexer of 16 heads: whole row tiles a token, so
    the index-scores kernel has its one-token path (ISSUE 44). No program is
    built: ``_dispatch_args`` counts on the host."""
    return _engine(_model(index_n_heads=16))


@pytest.mark.parametrize("heads,qlen,kvlen,want", [
    (16, [1, 1], [30, 52], 2 * 2),          # a decode-only plan
    (16, [1, 20], [30, 52], 2 * 1),         # a chunk: the decode row only
    (16, [0, 20], [0, 52], 0),              # no span of one token
    (4, [1, 1], [30, 52], 0),               # 4 wide rows are no row tile
])
def test_index_one_token_rows_on_the_dispatch_span(heads, qlen, kvlen, want):
    """Indexer layers x the step's spans of one token, where the kernel's
    own tiling (``dsa.index_grid_params``) gives them the path."""
    from paddle_tpu.kernels import dsa
    eng = _wide_indexer_engine() if heads == 16 else _engine(_model())
    assert eng.config.dsa.heads == heads and eng.config.dsa.layers == 2
    qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).tolist()
    packed = -(-sum(qlen) // 8) * 8
    args = eng._dispatch_args(qstart, qlen, kvlen, packed, qlen.count(1),
                              qlen.count(1), sum(q for q in qlen if q > 1))
    assert dsa.index_grid_params(heads, packed)["one_token"] == (heads == 16)
    assert args["index_one_token_rows"] == want
    assert args["index_query_rows"] == 2 * sum(qlen)


def test_served_over_http_and_metrics_tell_the_two_caches_apart():
    """``serve(model)`` at its defaults: a chunked prompt through the gateway
    equals the direct engine's stream, and ``/metrics`` carries the index
    keys' bytes a token beside the latent rows'."""
    import urllib.request
    from paddle_tpu.serving.server import serve
    from test_olmoe_serving import _complete
    model, prompt = _model(), _prompt(45, seed=9)
    _, want, _ = _serve_one(model, prompt, 3)
    srv = serve(model, port=0, num_slots=SLOTS, max_seq_len=96,
                prefill_chunk=32)
    try:
        assert _complete(srv, prompt, 3) == want
        with urllib.request.urlopen(srv.url + "/metrics", timeout=60) as r:
            text = r.read().decode()
    finally:
        srv.shutdown(drain=False, timeout=30)
    gauges = {ln.split()[0]: float(ln.split()[1])
              for ln in text.splitlines()
              if ln.startswith(("serving_index_bytes_per_token ",
                                "serving_kv_bytes_per_token "))}
    assert gauges == {"serving_index_bytes_per_token": 2 * 16 * 4,
                      "serving_kv_bytes_per_token": 5 * 128 * 4}


def test_the_shares_add_up():
    """Sixteen chips, each holding 2 of a 32-expert sigmoid router's experts:
    their routed parts (each through ``moe_ffn`` with its held range and the
    bias) plus the shared expert once equal the reference's uncut layer."""
    rng = np.random.default_rng(4)
    rows, hid, wid, n_exp, top = 24, 32, 16, 32, 8

    def rand(*s):
        return jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)

    g, router, bias = rand(rows, hid), rand(hid, n_exp) * 2, rand(n_exp) * 0.1
    w = {"w_gate": rand(n_exp, hid, wid), "w_up": rand(n_exp, hid, wid),
         "w_down": rand(n_exp, wid, hid)}
    shared = [rand(hid, wid), rand(hid, wid), rand(wid, hid)]
    hy = dict(top_k=top, norm_topk_prob=True, first_held=0, routed_scale=2.5)
    scores = jax.nn.sigmoid(g @ router)
    top_e, top_s = ref.route(scores, bias, jnp.full((rows, top), -1), hy)
    whole = ref._routed(g, top_e, top_s, w, hy) + ref._swiglu(g, *shared)
    parts, pairs = 0.0, 0
    for chip in range(16):
        held = slice(2 * chip, 2 * chip + 2)
        out, stats = moe_mod.moe_ffn(
            g, router, w["w_gate"][held], w["w_up"][held], w["w_down"][held],
            top_k=top, renormalize=True, first_held=2 * chip, scale=2.5,
            router_bias=bias)
        parts, pairs = parts + out, pairs + int(stats[0])
        assert int(stats[3]) == rows * top
    assert pairs == rows * top                      # every pick held once
    np.testing.assert_allclose(
        np.asarray(parts + decode_mod._swiglu_raw(g[None], *shared)[0]),
        np.asarray(whole), atol=1e-4, rtol=1e-4)


SWITCHES = {
    "quantize_weights": dict(quantize_weights=True),
    "tp > 1": dict(tp=2),
    "decode_ticks > 1": dict(decode_ticks=4),
    "spec_decode": dict(spec_decode=True),
    "decode_chunk > 1": dict(decode_chunk=8),
    "prefix_cache": dict(prefix_cache=True),
    "kv_dtype": dict(kv_dtype="int8"),
}


@pytest.mark.parametrize("switch", sorted(SWITCHES))
def test_unsupported_switch_raises(switch):
    with pytest.raises(ValueError) as e:
        serving_support.engine_as_given(_model(), **{**GEOMETRY,
                                                     **SWITCHES[switch]})
    assert "GlmMoeDsaForCausalLM" in str(e.value) \
        and "idx_layer" in str(e.value) and switch in str(e.value)
