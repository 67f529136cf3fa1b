"""MiMo-V2-Flash through the serving engine (ISSUE 56): a leading dense layer,
then periods of window-attention layers (a window of keys and a learned sink,
their own KV heads) and one full-attention layer, keys wider than values, every
layer after the first with a sigmoid-routed FFN; the full layers' keys and
values in the KV pool, the window layers' in rings by slot whose row is their
own: two stores of different rows under one manager.

The engine against the plain reference ON LOGITS
(``benchmark/reference_mimo_v2_flash.py``: a dense masked softmax with the
sink as one more column, every held expert over every row, float32): every
token the engine generates is produced from logits that equal the reference's
full forward at that position, for whole-prompt prefill then decode, for a
prompt through seven chunks (the ring of 56 rows wraps inside a chunk's span
and across chunks), for short and long rows in one batch, in a slot a longer
sequence used before. Tolerance 1e-4 of the largest logit: float32 on both
sides (conftest sets matmul precision ``highest``). Then each particular of
the model by a fault that must fail, the stores' geometry, the sixteen shares
of a layer's FFN adding up to the uncut layer, and every switch whose program
was not taught the layers raising. The module's engines share one set of
compiled programs (``JIT``), with the logits' recorder inside them.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models import mimo_v2_flash as mod
from paddle_tpu.models.mimo_v2_flash import MiMoV2FlashConfig
from paddle_tpu.serving import GenerationRequest

import serving_support
from serving_support import drain as _run, token_list as _prompt

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_mimo_v2_flash as ref  # noqa: E402

TOLERANCE = 1e-4
SLOTS = 3
CHUNK = 32
WIDTH = 256
GEOMETRY = dict(num_slots=SLOTS, max_seq_len=WIDTH, decode_chunk=1,
                prefill_chunk=CHUNK, prefix_block_size=8)


def _model(kernel="jnp", seed=7, **kw):
    return serving_support.model("mimo_v2_flash", seed=seed,
                                 decode_attention=kernel,
                                 max_position_embeddings=WIDTH, **kw)


@pytest.fixture(scope="module")
def model():
    return _model()


#: the programs of the module's one jnp model, compiled once: every test's
#: engine shares them (and the recorder inside them, ``_recorder``)
JIT = {}


def _reference_logits(model, ids, at):
    return serving_support.reference_logits(ref, model, ids, at, WIDTH)


def _deviation(model, seq, rows):
    return serving_support.deviation(ref, model, seq, rows, WIDTH)


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
    # name: (prompt length, new tokens); a chunk is 32, a ring 56 rows (the
    # window 16 + a chunk + a block of 8): 200 tokens wrap it three times
    "whole_prompt_then_decode": (21, 12),
    "seven_chunks_then_decode": (200, 6),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_equal_reference(case, model, rec):
    n_prompt, n_new = CASES[case]
    eng = _engine(model, rec)
    assert eng._ring_blocks * eng.cache.block_size == 56
    seq = eng.submit(GenerationRequest(_prompt(n_prompt),
                                       max_new_tokens=n_new))
    _run(eng)
    assert seq.done and len(seq.tokens) == n_new
    assert _deviation(model, seq, rec.rows[seq.request_id]) <= TOLERANCE
    if n_prompt > CHUNK:
        # chunks through the unified step, no whole-prompt program
        assert eng.stats["prefill_chunks"] == -(-n_prompt // CHUNK)
        assert eng.prefill_programs_asked == 0
    # six routed FFNs a program call; 4 of the router's 8 experts held
    assert eng.stats["moe_layer_calls"] % 6 == 0
    assert 0 < eng.stats["moe_pairs"] < eng.stats["moe_picks"]


def test_a_reused_slot_never_shows_its_last_tenant(model, rec):
    """A prompt of four chunks, then a SHORTER prompt of two chunks in the
    slot it left: the ring's stale rows from the slot's last tenant
    (positions 48-103 of the first prompt) lie where the second's window
    would look, and are never seen. (The Pallas kernel under these widths,
    the window and the sink is ``tests/test_pallas_ragged.py``'s, in interpret
    mode against the oracle these programs call; the engine's call of the
    kernel itself is the chip's: ``chip_smoke.py`` and the benchmark cell's
    check. Through the engine the interpreter's compile took 89 s.)"""
    eng = _engine(model, rec)
    first = eng.submit(GenerationRequest(_prompt(100, 1), max_new_tokens=1))
    _run(eng)
    assert first.done and first.slot == 0
    for held in eng.cache.window:   # the slot holds what it held
        assert np.abs(np.asarray(held[:, 0], np.float32)).max() > 0
    seq = eng.submit(GenerationRequest(_prompt(40, 2), max_new_tokens=1))
    _run(eng)
    assert seq.done and seq.slot == 0
    assert eng.prefill_programs_asked == 0
    for s in (first, seq):
        assert _deviation(model, s, rec.rows[s.request_id]) <= TOLERANCE


@pytest.mark.parametrize("kernel, ffn_rows", [
    ("jnp", mod.FFN_ROWS), ("pallas", mod.FFN_ROWS), ("jnp", 16)])
def test_forward_equals_reference(kernel, ffn_rows, monkeypatch):
    """The model's own whole-sequence forward against the reference at every
    position, and its picks against the reference's; the same with its FFNs
    run 16 positions at a time (70 positions: five blocks, the last padded
    with dead rows)."""
    monkeypatch.setattr(mod, "FFN_ROWS", ffn_rows)
    model = _model(kernel)
    ids = _prompt(70, 3)
    logits, picks = model.forward(np.asarray([ids], np.int32),
                                  return_router_picks=True)
    row = np.zeros((1, WIDTH), np.int32)
    row[0, :70] = ids
    want, probs = ref.logits_at(
        ref.weights_of(model), ref.hyper_of(model.config), row,
        np.arange(70)[None], with_router=True)
    want = np.asarray(want)[0]
    got = np.asarray(logits.value)[0]
    assert np.abs(got - want).max() / np.abs(want).max() <= TOLERANCE
    assert picks.shape == (6, 1, 70, 2)
    top = np.sort(np.argsort(np.asarray(probs), -1)[..., -2:], -1)
    assert (np.sort(np.asarray(picks), -1) == top).all()
    # (asked for no picks, the blocks carry none: the same logits)
    plain = model.forward(np.asarray([ids], np.int32))
    assert np.array_equal(np.asarray(plain.value)[0], got)


def test_short_and_long_rows_share_steps(model, rec):
    """A prompt of five chunks, a whole one and one of two chunks, decoding
    together: chunks and decode rows of different slots in one packed buffer,
    contexts of 15 to 170 in one batch, every ring at another place of its
    wrap."""
    eng = _engine(model, rec)
    seqs = [eng.submit(GenerationRequest(_prompt(n, seed=n),
                                         max_new_tokens=new))
            for n, new in ((150, 5), (11, 14), (50, 8))]
    _run(eng)
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
              theta=float(c.rope_theta), rotary=c.rotary_dim, swa=c.swa,
              moe=c.routing, return_picks=False)
    kw.update(statics)
    got, _ = mod._forward(model.decode_params()[0] if params is None
                          else params, jnp.asarray([ids], jnp.int32), **kw)
    want = _reference_logits(model, ids, range(48))
    return float(np.abs(np.asarray(got)[0] - want).max()
                 / np.abs(want).max())


def _window_trees(params, edit):
    """``params`` with ``edit(tree)`` applied to each place's window tree."""
    return dict(params, window_layers=tuple(
        edit(dict(t)) for t in params["window_layers"]))


def _full_trees(params, edit):
    """``params`` with ``edit(tree)`` applied to the dense layers' tree and
    to the periods' full layers' (the tree's own entries)."""
    out = edit(dict(params))
    out["dense_layers"] = edit(dict(params["dense_layers"]))
    return out


def _without(*names):
    return lambda tree: {k: v for k, v in tree.items() if k not in names}


def _sink_everywhere(monkeypatch):
    """A sink on the full layers too: their plain attention is told the
    tree's ``sink`` where it has one (the right program never reads it
    there)."""
    from paddle_tpu.serving import decode as decode_mod
    real = decode_mod._gqa_attend_plain
    monkeypatch.setattr(
        decode_mod, "_gqa_attend_plain",
        lambda q, k, v, lengths, window=None, sink=None: real(
            q, k, v, lengths, window=window,
            sink=jnp.full((q.shape[2],), 4.0) if sink is None else sink))
    _own_jit(monkeypatch)


def _own_jit(monkeypatch):
    """A jit of its own: no static number changes and jit's cache is by
    function (the right forward was traced at these very arguments)."""
    real = mod._forward.__wrapped__
    monkeypatch.setattr(mod, "_forward", jax.jit(
        lambda params, ids, **kw: real(params, ids, **kw),
        static_argnames=("nh", "nkv", "hd", "eps", "theta", "rotary", "swa",
                         "moe", "return_picks", "ffn_rows")))


def _four_kv_heads(tree):
    """A window layer on the FULL layers' KV heads: the first 2 of its 4
    (the published 4 of 8), each then serving twice the query heads."""
    for name, width in (("wk", 24), ("wv", 16)):
        tree[name] = tree[name][..., :2 * width]
    return tree


def _wo_fed_a_key_wide_head(tree):
    """``W_o`` fed heads as wide as a KEY: the values padded to 24 (zeros past
    16) and ``W_o``'s rows taken in the order of that wider layout, so head
    ``h``'s values meet the rows of head ``h * 24 / 16``'s."""
    if "wv" in tree:
        P, H, wide = tree["wv"].shape
        wv = tree["wv"].reshape(P, H, -1, 16)
        tree["wv"] = jnp.pad(wv, [(0, 0)] * 3 + [(0, 8)]).reshape(P, H, -1)
        wo = tree["wo"]
        tree["wo"] = jnp.pad(wo, [(0, 0), (0, wo.shape[1] // 2), (0, 0)])
    return tree


def _dense_at_the_experts_width(tree):
    """The dense layer's SwiGLU cut to ``moe_intermediate_size`` (32 of its
    96 units): layer 0 read as one more expert-sized FFN."""
    tree = dict(tree)
    dense = dict(tree["dense_layers"])
    for name in ("w_gate", "w_up"):
        dense[name] = dense[name][..., :32]
    dense["w_down"] = dense["w_down"][:, :32]
    tree["dense_layers"] = dense
    return tree


def _no_selection_bias(params):
    """The picks by ``s`` alone: the bias decides near-ties, so a pick or two
    of the sequence changes hands."""
    def edit(tree):
        if "router_bias" in tree:
            tree["router_bias"] = tree["router_bias"] * 0.0
        return tree
    return _window_trees(edit(dict(params)), edit)


#: name: (edit of the tree, static numbers to replace as a function of the
#: configuration, a patch to apply first); None where there is none
FAULTS = {
    "sink_dropped": (
        lambda p: _window_trees(p, lambda t: dict(
            t, sink=jnp.full_like(t["sink"], -1e9))), None, None),
    "sink_on_the_full_layers_too": (None, None, _sink_everywhere),
    "window_of_15": (
        None, lambda c: dict(swa=c.swa._replace(window=15)), None),
    "window_of_17": (
        None, lambda c: dict(swa=c.swa._replace(window=17)), None),
    "thetas_swapped": (
        None, lambda c: dict(theta=c.swa.theta,
                             swa=c.swa._replace(theta=float(c.rope_theta))),
        None),
    "whole_head_rotated": (None, lambda c: dict(rotary=None), None),
    "value_scale_dropped": (
        None, lambda c: dict(swa=c.swa._replace(v_scale=1.0)), None),
    "wo_fed_a_key_wide_head": (
        lambda p: _window_trees(_full_trees(p, _wo_fed_a_key_wide_head),
                                _wo_fed_a_key_wide_head), None, None),
    "four_kv_heads_in_a_window_layer": (
        lambda p: _window_trees(p, _four_kv_heads), None, None),
    "selection_bias_dropped": (_no_selection_bias, None, None),
    "weights_not_renormalised": (
        None, lambda c: dict(moe=(c.routing[0], False) + c.routing[2:]),
        None),
    "dense_layer_at_the_experts_width": (
        _dense_at_the_experts_width, None, None),
}


ONE_PERIOD = dict(num_hidden_layers=4, hybrid_layer_pattern=[0, 1, 1, 0],
                  moe_layer_freq=[0, 1, 1, 1])


@pytest.fixture(scope="module")
def small():
    """A dense layer and one period (two window layers and a full one): what
    a fault's own trace costs is its four layers'."""
    return _model(**ONE_PERIOD)


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


def test_weights_come_from_the_unbiased_scores():
    """``kernels.moe_ffn``'s sigmoid rule with nothing beside it: the picks by
    ``s + c``, the weights ``s_e / sum_picked s``; weights taken from ``s +
    c`` differ by far more than the tolerance at a bias the size of a
    score."""
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal((12, 32)) * 0.5, jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(8) * 0.3, jnp.float32)
    w = {n: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
         for n, s in (("w_gate", (8, 32, 16)), ("w_up", (8, 32, 16)),
                      ("w_down", (8, 16, 32)))}
    hy = {"top_k": 3, "norm_topk_prob": True, "routed_scale": 1.0,
          "first_held": 0}
    with jax.default_matmul_precision("highest"):
        want, probs = ref.routed_ffn(
            g, dict(router=router, router_bias=bias, **w),
            jnp.full((12, 3), -1), hy)
        got, _ = moe_mod.moe_ffn(g, router, *(w[n] for n in ref.EXPERTS),
                                 top_k=3, renormalize=True,
                                 router_bias=bias)
        s = jax.nn.sigmoid(g @ router)
        top = jax.lax.top_k(s + bias, 3)[1]
        biased = jnp.take_along_axis(s + bias, top, -1)
        wrong = sum(
            (biased / biased.sum(-1, keepdims=True))[:, j, None]
            * jax.vmap(lambda x, e: ref._swiglu(
                x, w["w_gate"][e], w["w_up"][e], w["w_down"][e]))(
                    g, top[:, j]) for j in range(3))
    scale = np.abs(np.asarray(want)).max()
    assert np.abs(np.asarray(got - want)).max() <= TOLERANCE * scale
    assert np.abs(np.asarray(wrong - want)).max() > 100 * TOLERANCE * scale
    assert np.allclose(np.asarray(probs), np.asarray(s + bias))


# ------------------------------------------------------------ the two stores
def test_two_stores_of_different_rows(model):
    c = model.config
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    assert (c.num_hidden_layers, c.num_window_layers, c.num_kv_layers,
            c.num_dense_layers, c.num_periods) == (7, 4, 3, 1, 2)
    # three pool layers (the dense layer's and the two full layers'), a row a
    # token: 2 KV heads, a key 24 wide and a value 16
    assert eng.cache.pool.k.shape[0] == 3 == eng.cache.pool.v.shape[0]
    assert (eng.cache.pool.k.shape[-1], eng.cache.pool.v.shape[-1]) == (
        2 * 24, 2 * 16)
    assert eng.cache.bytes_per_token() == 3 * 2 * (24 + 16) * 4
    # four rings a slot of 7 blocks of 8 (the window 16 + a chunk of 32 + a
    # block), a row of the window layers' own: 4 KV heads
    keys, values = eng.cache.window
    assert keys.shape == (4, SLOTS, 7, 8, 4 * 24)
    assert values.shape == (4, SLOTS, 7, 8, 4 * 16)
    assert eng.cache.window_bytes_per_slot == 4 * 56 * 4 * (24 + 16) * 4
    assert eng.cache.state is None
    doc = eng.cache.occupancy_bytes()
    assert doc["capacity_window"] == SLOTS * eng.cache.window_bytes_per_slot
    assert doc["capacity_kv"] == eng.cache.pool.num_blocks * 8 \
        * eng.cache.bytes_per_token()
    assert doc["used_window"] == 0 == doc["used_kv"]
    seq = eng.submit(GenerationRequest(_prompt(40), max_new_tokens=2))
    eng.step()
    doc = eng.cache.occupancy_bytes()
    # one slot's rings whatever the length; the pool's blocks by the tokens
    assert doc["used_window"] == eng.cache.window_bytes_per_slot
    assert doc["used_kv"] >= 32 * eng.cache.bytes_per_token()
    _run(eng)
    assert seq.done and eng.cache.occupancy_bytes()["used_window"] == 0


def test_the_engine_counts_each_kind_of_layers_call(model):
    """The ``dispatch`` span's args: a full layer's call over the pool and a
    window layer's over its ring, whose keys are the window's and whose
    fetch is whole groups of blocks."""
    eng = serving_support.engine_as_given(model, **GEOMETRY)
    work = eng._dispatch_args(
        np.asarray([0, 1, 2]), np.asarray([1, 1, 30]),
        np.asarray([100, 9, 130]), 35, 2, 2, 30)
    assert work["kv_tokens"] == 100 + 9 + 130
    assert work["window_kv_tokens"] == 16 + 9 + (30 + 15)
    assert work["window_attn_pairs"] == 16 + 9 + 30 * 16
    # (whole blocks of whole groups from the group the window starts in)
    assert work["window_fetched_keys"] >= work["window_kv_tokens"]
    assert work["window_fetched_keys"] % eng.cache.block_size == 0
    assert "cross_rows" not in work


def test_the_published_sizes():
    # the defaults are the published 48 layers, which are refused (a short
    # run of four window layers); their whole periods are what runs
    with pytest.raises(ValueError, match="short run"):
        MiMoV2FlashConfig()
    assert len(mod.PUBLISHED_PATTERN) == 48 \
        and mod.PUBLISHED_PATTERN.count(0) == 9
    c = MiMoV2FlashConfig(
        num_hidden_layers=43, moe_layer_freq=[0] + [1] * 42,
        hybrid_layer_pattern=[0] + mod.PUBLISHED_PATTERN[6:])
    assert (c.num_hidden_layers, c.num_kv_layers, c.num_window_layers,
            c.window_per_period, c.num_periods) == (43, 8, 35, 5, 7)
    assert c.rotary_dim == 64 and c.rms_norm_eps == 1e-5
    assert c.swa == (128, 10000.0, 0.707, 0)
    assert c.routing == (8, True, 1, 1, 0, 1.0)
    share = MiMoV2FlashConfig(
        n_routed_experts=16, router_experts=256, first_held_expert=240,
        num_hidden_layers=7, hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 0],
        moe_layer_freq=[0] + [1] * 6)
    assert share.routing[4] == 240 and share.num_window_layers == 5
    seven = dict(num_hidden_layers=7, moe_layer_freq=[0] + [1] * 6)
    cell = dict(seven, hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 0])
    for bad, why in (
            (dict(seven, hybrid_layer_pattern=[1, 1, 1, 1, 1, 0, 0]),
             "all full attention"),             # a window layer first
            (dict(seven, hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 1]),
             "no period ends in a full layer"),
            (dict(seven, hybrid_layer_pattern=[0, 0, 1, 1, 0, 1, 0]),
             "starts with a full layer"),
            (dict(seven, hybrid_layer_pattern=[0, 1, 1, 0, 1, 1, 1]),
             "period"),
            (dict(num_hidden_layers=7, hybrid_layer_pattern=[0] * 7,
                  moe_layer_freq=[1] * 7), "prefix"),
            (dict(cell, n_routed_experts=16, router_experts=256,
                  first_held_expert=241), "held experts"),
            (dict(cell, add_full_attention_sink_bias=True), "sink"),
            (dict(cell, swa_head_dim=128), "share")):
        with pytest.raises(ValueError, match=why):
            MiMoV2FlashConfig(**bad)


@pytest.mark.parametrize("switch", serving_support.OTHER_SWITCHES,
                         ids=lambda s: next(iter(s)))
def test_every_other_switch_raises_by_name(switch, model):
    geometry = {**GEOMETRY, **switch}
    with pytest.raises(ValueError, match="window_layers") as e:
        serving_support.engine_as_given(model, **geometry)
    assert all(name in str(e.value) for name in switch)


def test_served_over_http(model):
    """``serve(model)`` at its defaults: a chunked prompt through the gateway
    equals the model's own forward, and ``/metrics`` carries the rings' bytes
    a slot beside the pool's a token and the routing's counters."""
    import urllib.request
    from paddle_tpu.serving.server import serve
    from test_olmoe_serving import _complete
    prompt = _prompt(45, seed=9)
    srv = serve(model, port=0, num_slots=SLOTS, max_seq_len=WIDTH,
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
              if ln.startswith(("serving_window_bytes_per_slot ",
                                "serving_kv_bytes_per_token "))}
    assert gauges == {
        "serving_window_bytes_per_slot": cache.window_bytes_per_slot,
        "serving_kv_bytes_per_token": 3 * 2 * (24 + 16) * 4}
    assert "serving_moe_experts_touched_total" in text


# ------------------------------------------------------- the routed FFN alone
def test_the_shares_add_up():
    """Sixteen chips, each holding 2 of a 32-expert router's experts: their
    routed parts (each through ``moe_ffn`` with its held range, the sigmoid
    rule with the selection bias) equal the reference's uncut layer; there is
    no shared expert to count once."""
    n_exp, top, rows, hid, wid = 32, 5, 24, 32, 16
    rng = np.random.default_rng(4)

    def rand(*s):
        return jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)

    g, router, bias = rand(rows, hid), rand(hid, n_exp) * 2, rand(n_exp) * 0.1
    w = {"w_gate": rand(n_exp, hid, wid), "w_up": rand(n_exp, hid, wid),
         "w_down": rand(n_exp, wid, hid)}
    hy = {"top_k": top, "norm_topk_prob": True, "routed_scale": 1.0,
          "first_held": 0}
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_ffn(
            g, dict(router=router, router_bias=bias, **w),
            jnp.full((rows, top), -1), hy)
        got, pairs = jnp.zeros_like(want), 0
        for first in range(0, n_exp, 2):
            part, stats = moe_mod.moe_ffn(
                g, router, *(w[n][first:first + 2] for n in ref.EXPERTS),
                top_k=top, renormalize=True, first_held=first,
                router_bias=bias)
            got, pairs = got + part, pairs + int(stats[0])
    assert pairs == rows * top              # every pick lands on one share
    assert np.abs(np.asarray(got - want)).max() \
        <= TOLERANCE * np.abs(np.asarray(want)).max()
