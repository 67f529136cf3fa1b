"""DeepSeek-V2 through the serving engine (ISSUE 31): the engine against the
plain float32 reference ON LOGITS (whole-prompt prefill then decode through
the latent pool; chunks through the unified step then decode; both attention
paths), two requests of unequal length in one step, the routing's picks, the
YaRN numbers by hand, the shares of an expert-parallel layer adding up to
the uncut layer, OLMoE's routed FFN unchanged, and every switch whose
program was not taught the layer raising."""
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models import deepseek_v2 as dsv2
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving import decode as decode_mod

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))
sys.path.insert(0, HERE)
import reference_deepseek_v2 as ref  # noqa: E402
import reference_olmoe  # noqa: E402
import serving_support  # noqa: E402
from test_olmoe_serving import (GEOMETRY, _engine, _prompt,  # noqa: E402
                                _serve_recording_logits)

TOLERANCE = 1e-4


def _model(attention="jnp", fresh=False):
    """The process's model; ``fresh`` one of its own for a test that reads
    the routing record its step programs write on it."""
    build = serving_support.fresh_model if fresh else serving_support.model
    return build("deepseek_v2", seed=11, decode_attention=attention)


def _reference_logits(model, prompt, tokens):
    ids = np.asarray([prompt + tokens], np.int32)
    at = np.asarray([[len(prompt) - 1 + k for k in range(len(tokens))]])
    return np.asarray(ref.logits_at(ref.weights_of(model),
                                    ref.hyper_of(model.config), ids, at))[0]


CASES = {
    # name: (prompt length, new tokens, attention path)
    "whole_prompt_then_decode": (21, 8, "jnp"),
    "whole_prompt_then_decode_kernel": (13, 4, "pallas"),
    "chunked_then_decode": (75, 5, "jnp"),
    "chunked_then_decode_kernel": (40, 3, "pallas"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_logits_equal_reference(case, monkeypatch):
    n_prompt, n_new, attention = CASES[case]
    model = _model(attention)
    prompt = _prompt(n_prompt)
    eng, tokens, rows = _serve_recording_logits(model, prompt, n_new,
                                                monkeypatch)
    want = _reference_logits(model, prompt, tokens)
    assert np.abs(rows - want).max() / np.abs(want).max() <= TOLERANCE
    if n_prompt > GEOMETRY["prefill_chunk"]:
        assert eng.prefill_programs_asked == 0      # chunks only
        assert eng.stats["prefill_chunks"] == -(-n_prompt // 32)
    # the routing summary rode the fetches: picks over the router's width,
    # pairs only for those that landed on the held half
    c = model.config
    assert eng.stats["moe_layer_calls"] % (
        c.num_hidden_layers - c.first_k_dense_replace) == 0
    assert eng.stats["moe_picks"] % c.num_experts_per_tok == 0
    assert 0 < eng.stats["moe_pairs"] < eng.stats["moe_picks"]


def test_wrong_scale_fails(monkeypatch):
    """A dropped ``mscale`` (the softmax scale without YaRN's factor) moves
    the logits far past the tolerance: the comparison above would fail."""
    model = _model(fresh=True)
    real = type(model.config).mla
    monkeypatch.setattr(
        type(model.config), "mla", property(lambda c: real.fget(c)._replace(
            scale=c.head_dim ** -0.5)))
    prompt = _prompt(21)
    _, tokens, rows = _serve_recording_logits(model, prompt, 4, monkeypatch,
                                              fresh=True)
    want = _reference_logits(model, prompt, tokens)
    assert np.abs(rows - want).max() / np.abs(want).max() > 1e-3


def test_two_requests_of_unequal_length_in_one_step(attention="jnp"):
    """A long prompt's chunks and a short request's decode rows share the
    unified step; every served token is the reference's best (the
    benchmark's rule, at float32's tolerance)."""
    model = _model(attention)
    eng = _engine(model)
    prompts = [_prompt(9, seed=1), _prompt(70, seed=2)]
    seqs = [eng.submit(GenerationRequest(p, max_new_tokens=6))
            for p in prompts]
    while eng.has_work():
        eng.step()
    assert eng.stats["prefill_chunks"] == 3
    for p, s in zip(prompts, seqs):
        want = _reference_logits(model, p, list(s.tokens))
        for row, t in zip(want, s.tokens):
            assert row.max() - row[t] <= TOLERANCE * np.abs(row).max()


def test_group_limited_picks_equal_reference():
    model = _model()
    ids = np.random.RandomState(3).randint(1, 256, (2, 33))
    _, picks = model.forward(ids, return_router_picks=True)
    at = np.tile(np.arange(ids.shape[1])[None], (2, 1))
    _, scores = ref.logits_at(ref.weights_of(model),
                              ref.hyper_of(model.config), ids, at,
                              with_router=True)
    scores, picks = np.asarray(scores), np.asarray(picks)
    k = model.config.num_experts_per_tok
    assert picks.shape == scores.shape[:-1] + (k,)
    want = np.sort(np.argsort(scores, -1)[..., -k:], -1)
    assert (np.sort(picks, -1) == want).all()
    # one group of the two a token: its picks share a group
    assert (picks[..., 0] // 4 == picks[..., 1] // 4).all()


@pytest.mark.parametrize("n_prompt", [21, 75])
def test_served_picks_are_the_step_programs(n_prompt):
    """The routing record: the experts the whole-prompt prefill or the
    chunks, and then the decode rows, picked, read back by position; the
    last sampled token is never fed back. In float32 they are the
    reference's own rule, and ``forward`` hands them on."""
    model = _model(fresh=True)
    prompt, n_new = _prompt(n_prompt), 5
    eng = _engine(model)
    seq = eng.submit(GenerationRequest(prompt, max_new_tokens=n_new))
    while eng.has_work():
        eng.step()
    assert model.served_router_picks([_prompt(n_prompt, seed=9)]) is None
    ids = np.zeros((1, n_prompt + n_new + 3), np.int32)     # padded, as the
    ids[0, :n_prompt + n_new] = prompt + list(seq.tokens)   # benchmark does
    served = model.served_router_picks(ids)
    c = model.config
    ran = n_prompt + n_new - 1
    assert served.shape == (c.num_hidden_layers - c.first_k_dense_replace,
                            1, ids.shape[1], c.num_experts_per_tok)
    assert (served[:, :, :ran] >= 0).all() and (served[:, :, ran:] == -1).all()
    at = np.arange(ran)[None]
    w = ref.weights_of(model)
    assert w["served_picks"] is not None
    _, scores = ref.logits_at({**w, "served_picks": None},
                              ref.hyper_of(c), ids, at, with_router=True)
    want = np.sort(np.argsort(np.asarray(scores), -1)[
        ..., -c.num_experts_per_tok:], -1)
    assert (np.sort(served[:, :, :ran], -1) == want).all()
    _, picks = model.forward(ids, return_router_picks=True)
    assert (np.asarray(picks)[:, :, :ran] == served[:, :, :ran]).all()


def test_served_picks_through_the_http_server():
    """``serve(model)`` with no switch: what the benchmark's check does. Two
    requests at once through chunks of the unified step; the record then
    holds both, each row by its own content, as the reference asks for
    them."""
    import threading
    from paddle_tpu.serving.server import serve
    from test_olmoe_serving import _complete
    model = _model(fresh=True)
    srv = serve(model, port=0, num_slots=2, max_seq_len=96, prefill_chunk=32)
    try:
        prompts = [_prompt(70, seed=3), _prompt(41, seed=4)]
        out = [None, None]
        ths = [threading.Thread(target=lambda i=i: out.__setitem__(
            i, _complete(srv, prompts[i], 4))) for i in range(2)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(300)
    finally:
        srv.shutdown(drain=False, timeout=30)
    ids = np.zeros((2, 80), np.int32)
    for i, (p, o) in enumerate(zip(prompts, out)):
        ids[i, :len(p) + 4] = p + o
    served = ref.weights_of(model)["served_picks"](ids)
    assert (served[:, 0, :73] >= 0).all() and (served[:, 0, 73:] == -1).all()
    assert (served[:, 1, :44] >= 0).all() and (served[:, 1, 44:] == -1).all()
    _, picks = model.forward(ids, return_router_picks=True)
    assert (np.asarray(picks)[:, 0, :73] == served[:, 0, :73]).all()


def _rolled_experts(h, router, w_gate, w_up, w_down, **kw):
    # the held stack one expert out of place: what a wrong first_held or a
    # wrong order of the held experts does ([L, E, ...]: roll the expert axis)
    return moe_mod.moe_ffn(h, router, jnp.roll(w_gate, 1, 1),
                           jnp.roll(w_up, 1, 1), jnp.roll(w_down, 1, 1),
                           **kw)


def _forgot_scale(h, *w, scale=1.0, **kw):
    return moe_mod.moe_ffn(h, *w, **kw)


@pytest.mark.parametrize("fault", ["rolled_experts", "forgot_scale"])
def test_teacher_forced_reference_sees_the_held_experts(fault, monkeypatch):
    """A fault in the held experts' chain of the STEP PROGRAMS (never in the
    router, so the picks are the rule's): the reference, following the served
    picks, leaves the served logits by far more than the tolerance."""
    monkeypatch.setattr(decode_mod, "moe_ffn", globals()["_" + fault])
    model = _model(fresh=True)
    prompt = _prompt(75)
    _, tokens, rows = _serve_recording_logits(model, prompt, 4, monkeypatch,
                                              fresh=True)
    want = _reference_logits(model, prompt, tokens)
    assert np.abs(rows - want).max() / np.abs(want).max() > 1e-2


def test_reference_follows_the_picks_it_is_told():
    """Told other experts, the reference uses them, each at its own router's
    score; told nothing (-1), its own rule."""
    model = _model(fresh=True)
    ids = np.random.RandomState(5).randint(1, 256, (1, 17))
    at = np.arange(17)[None]
    w, hy = ref.weights_of(model), ref.hyper_of(model.config)
    assert model.served_router_picks(ids) is None       # nothing served
    own = np.asarray(ref.logits_at(w, hy, ids, at))
    none = np.full((2, 1, 17, 2), -1, np.int32)
    told = np.asarray(ref.logits_at({**w, "served_picks": lambda _: none},
                                    hy, ids, at))
    np.testing.assert_array_equal(own, told)
    _, picks = model.forward(ids, return_router_picks=True)
    same = np.asarray(ref.logits_at(
        {**w, "served_picks": lambda _: np.asarray(picks)}, hy, ids, at))
    np.testing.assert_allclose(own, same, atol=1e-5)
    other = (np.asarray(picks) + 1) % 4         # held experts, but not its own
    moved = np.asarray(ref.logits_at(
        {**w, "served_picks": lambda _: other}, hy, ids, at))
    assert np.abs(moved - own).max() / np.abs(own).max() > 1e-2


def test_routing_record_is_bounded():
    """The oldest programs' picks go first; a sequence whose first rows went
    is no longer found."""
    from paddle_tpu.serving.routing_record import RoutingRecord

    class Seq:
        def __init__(self, prompt):
            self.prompt, self.tokens = np.asarray(prompt, np.int32), []

    rec = RoutingRecord(max_bytes=3 * 2 * 8 * 2 * 4)       # three calls' worth
    a, b = Seq([5, 6, 7, 8]), Seq([9, 9, 9])
    for step in range(2):                       # a: two chunks of two rows
        rec.note(jnp.full((2, 8, 2), step, jnp.int32),
                 [(a, 3, 2, 2 * step)])
    a.tokens = [4]
    assert (rec.lookup([5, 6, 7, 8, 4, 0])[0, :, 0] ==
            [0, 0, 1, 1, -1, -1]).all()
    rec.note(jnp.full((2, 8, 2), 7, jnp.int32), [(b, 0, 3, 0)])
    rec.note(jnp.full((2, 8, 2), 7, jnp.int32), [])        # no live row: not kept
    assert rec.lookup([9, 9, 9]) is not None and len(rec._calls) == 3
    rec.note(jnp.full((2, 8, 2), 8, jnp.int32), [(a, 0, 1, 4)])
    assert len(rec._calls) == 3 and rec.lookup([5, 6, 7, 8, 4]) is None
    assert rec.lookup([1, 2, 3]) is None


def test_yarn_frequencies_and_mscale_by_hand():
    """The published numbers: rope 64, theta 1e4, factor 40, original 4096,
    beta 32 / 1. Correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) =
    10.47 -> 10, and 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23."""
    assert math.floor(64 * math.log(4096 / (32 * 2 * math.pi))
                      / (2 * math.log(1e4))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi))
                     / (2 * math.log(1e4))) == 23
    inv = np.asarray(dsv2.yarn_inv_freq(64, 1e4, 40, 4096, 32, 1))
    plain = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13                       # frequency 16, between
    np.testing.assert_allclose(
        inv[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(inv, ref.yarn_frequencies(
        64, 1e4, (40, 4096, 32, 1, 0.707, 0.707)), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4 and dsv2.yarn_mscale(40, 0.707) == m
    mla = dsv2.DeepseekV2Config().mla
    assert abs(mla.scale - 192 ** -0.5 * m * m) < 1e-9
    assert mla.yarn[4] == 1.0                   # mscale / mscale_all_dim
    assert (mla.rank, mla.nope, mla.rope, mla.v) == (512, 128, 64, 128)


def test_the_shares_add_up():
    """Eight chips, each holding one routing group of a 16-expert router:
    their routed parts (each through ``moe_ffn`` with its held range) plus
    the shared expert once equal the reference's uncut layer."""
    rng = np.random.default_rng(4)
    rows, hid, wid, n_exp = 24, 32, 16, 16

    def rand(*s):
        return jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)

    g, router = rand(rows, hid), rand(hid, n_exp) * 4
    w = {"w_gate": rand(n_exp, hid, wid), "w_up": rand(n_exp, hid, wid),
         "w_down": rand(n_exp, wid, hid)}
    shared = [rand(hid, 2 * wid), rand(hid, 2 * wid), rand(2 * wid, hid)]
    hy = dict(top_k=4, norm_topk_prob=False, n_group=8, topk_group=3,
              first_held=0, routed_scale=16.0)
    raw = jnp.asarray(np.asarray(moe_mod.jax.nn.softmax(g @ router, -1)))
    own = jnp.full((rows, 4), -1, jnp.int32)        # the rule's own picks
    whole = ref._routed(g, raw, own, w, hy) + ref._swiglu(g, *shared)
    parts, pairs = 0.0, 0
    for chip in range(8):
        held = slice(2 * chip, 2 * chip + 2)
        out, stats = moe_mod.moe_ffn(
            g, router, w["w_gate"][held], w["w_up"][held], w["w_down"][held],
            top_k=4, n_group=8, topk_group=3, first_held=2 * chip,
            scale=16.0)
        parts, pairs = parts + out, pairs + int(stats[0])
        assert int(stats[3]) == rows * 4            # picks made: all of them
    assert pairs == rows * 4                        # every pick held once
    np.testing.assert_allclose(
        np.asarray(parts + decode_mod._swiglu_raw(g[None], *shared)[0]),
        np.asarray(whole), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("renormalize", [False, True])
def test_olmoe_routed_ffn_unchanged(renormalize):
    """Every expert held, one group, no scale: the same function is OLMoE's
    layer, equal to OLMoE's own plain reference."""
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((19, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    w = {n: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
         for n, s in (("w_gate", (8, 32, 16)), ("w_up", (8, 32, 16)),
                      ("w_down", (8, 16, 32)))}
    out, stats = moe_mod.moe_ffn(h, router, w["w_gate"], w["w_up"],
                                 w["w_down"], top_k=2,
                                 renormalize=renormalize)
    probs = moe_mod.jax.nn.softmax(h @ router, -1)
    want = reference_olmoe._experts(h, probs, w, 2, renormalize)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5,
                               rtol=1e-4)
    assert int(stats[0]) == int(stats[3]) == 19 * 2


SWITCHES = {
    "quantize_weights": dict(quantize_weights=True),
    "quantize_activations": dict(quantize_weights=True,
                                 quantize_activations=True),
    "tp > 1": dict(tp=2),
    "decode_ticks > 1": dict(decode_ticks=4),
    "spec_decode": dict(spec_decode=True),
    "decode_chunk > 1": dict(decode_chunk=8),
    "prefix_cache": dict(prefix_cache=True),
    "kv_dtype": dict(kv_dtype="int8"),
    "kv_dtype fp8": dict(kv_dtype="fp8"),
}


@pytest.mark.parametrize("switch", sorted(SWITCHES))
def test_unsupported_switch_raises(switch):
    model = _model()
    with pytest.raises(ValueError) as e:
        _engine(model, **SWITCHES[switch])
    assert "DeepseekV2ForCausalLM" in str(e.value) \
        and switch.split(" fp8")[0] in str(e.value)
