"""The serving engine against an oracle that shares no serving code.

``served_equals_forward``: teacher-forced ``model.forward`` (the training
path, ``models/llama.py`` / ``models/olmoe.py``) over prompt + served
tokens must rank every served token within ``TOLERANCE`` of the best logit
at its position, the rule of ``benchmark/kinds/serve._reference_check``:
``max_j ref[i, j] - ref[i, t] <= TOLERANCE * max_j |ref[i, j]|``. Both
sides are float32 at matmul precision ``highest`` (conftest), so what is
left is summation order, about 1e-6; a near-tie inside the tolerance may
change hands, anything else is a wrong token. It judges greedy streams,
whatever path produced them: whole-prompt prefill, chunks riding the
unified step, a prefix hit's suffix prefill, recompute after a
displacement, the fused decode tail.

A sampled stream has no argmax to compare with: what holds it is that a
request's tokens depend on its own prompt and key only, never on how the
engine was configured to schedule or cache (``test_stream_invariant_
under_policy``).

``test_removed_switches_are_gone`` keeps the surface shut: the engine has
one cache and one step, and no argument or flag selects another.
"""
import inspect

import numpy as np
import pytest

from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest

import serving_support
from serving_support import BS, CHUNK, drain as _drain, engine as _engine

TOLERANCE = 1e-4


def served_equals_forward(model, prompt, tokens, tolerance=TOLERANCE):
    """Assert that every served token is the forward pass's (near-)argmax
    at its position. Returns the worst share of the largest logit by which
    a served token fell short of the best."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    tokens = [int(t) for t in tokens]
    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = np.asarray(model.forward(seq[None]).value, np.float32)[0]
    assert np.isfinite(logits).all()
    worst = 0.0
    for k, t in enumerate(tokens):
        row = logits[len(prompt) - 1 + k]     # predicts served token k
        short = float((row.max() - row[t]) / max(np.abs(row).max(), 1e-9))
        assert short <= tolerance, (
            f"served token {k} ({t}) is {short:.3g} of the largest logit "
            f"below the forward pass's best ({int(row.argmax())})")
        worst = max(worst, short)
    return worst


@pytest.fixture(scope="module")
def llama():
    return serving_support.model("llama", seed=28)   # GQA: nkv=2 < nh=4


@pytest.fixture(scope="module")
def olmoe():
    return serving_support.model("olmoe", seed=7)


def _prompt(seed, n):
    return serving_support.prompt(seed, n, low=1)


# Each case drives one engine and returns the (prompt, served tokens) pairs
# to judge; its own asserts show that the path it is named for really ran.
def _whole_prompt(model, n):
    eng = _engine(model)
    seq = eng.submit(GenerationRequest(_prompt(n, n), max_new_tokens=6))
    _drain(eng)
    assert eng.stats["prefill_chunks"] == 0 and eng.stats["prefills"] == 1
    return [(seq.prompt, seq.tokens)]


def _chunked(model):
    eng = _engine(model)
    seq = eng.submit(GenerationRequest(_prompt(3, 50), max_new_tokens=6))
    _drain(eng)
    assert eng.stats["prefill_chunks"] == 4         # ceil(50 / 16)
    return [(seq.prompt, seq.tokens)]


def _staggered(model):
    """A decode row and a prefill chunk in one step, several times."""
    eng = _engine(model, headroom_mult=None)
    first = eng.submit(GenerationRequest(_prompt(4, 9), max_new_tokens=12))
    eng.step()
    eng.step()
    late = eng.submit(GenerationRequest(_prompt(5, 50), max_new_tokens=5))
    mixed = 0
    while eng.has_work():
        chunks, toks = eng.stats["prefill_chunks"], len(first.tokens)
        eng.step()
        mixed += (eng.stats["prefill_chunks"] > chunks
                  and len(first.tokens) > toks)
    assert mixed >= 3 and late.status == first.status == "finished"
    return [(first.prompt, first.tokens), (late.prompt, late.tokens)]


def _prefix_hit(model, tail):
    """The second request shares a 20-token system prompt with the first:
    two blocks install by reference, the rest is a suffix prefill (``tail``
    6) or chunks from the resume offset (``tail`` 40)."""
    eng = _engine(model, prefix_cache=True)
    system = _prompt(6, 20)
    pairs = []
    for seed, n in ((7, 6), (8, tail)):
        chunks = eng.stats["prefill_chunks"]
        seq = eng.submit(GenerationRequest(
            np.concatenate([system, _prompt(seed, n)]), max_new_tokens=6))
        _drain(eng)
        pairs.append((seq.prompt, seq.tokens))
    assert seq.prefix_hit_tokens == 2 * BS
    assert (eng.stats["prefill_chunks"] > chunks) == (tail > CHUNK)
    return pairs


def _displaced(model):
    """Evicted mid-decode and restored: KV rebuilt by recompute, the
    stream continues where it stopped."""
    eng = _engine(model)
    seq = eng.submit(GenerationRequest(_prompt(9, 21), max_new_tokens=10))
    while len(seq.tokens) < 4:
        eng.step()
    assert eng.evict(seq) and seq.slot is None
    assert eng.restore(seq)
    _drain(eng)
    assert eng.stats["restores"] == 1 and len(seq.tokens) == 10
    return [(seq.prompt, seq.tokens)]


def _fused_tail(model, decode_chunk):
    eng = _engine(model, decode_chunk=decode_chunk)
    seqs = [eng.submit(GenerationRequest(_prompt(s, n), max_new_tokens=20))
            for s, n in ((10, 7), (11, 13))]
    _drain(eng)
    # 19 decode tokens after token 0: one sync each, or fused blocks of 8
    assert (eng.stats["decode_calls"] == 19) == (decode_chunk == 1)
    assert eng.stats["decode_steps"] == 19
    return [(s.prompt, s.tokens) for s in seqs]


def _olmoe(model, n):
    eng = _engine(model, num_slots=3, prefill_chunk=32)
    seq = eng.submit(GenerationRequest(_prompt(n, n), max_new_tokens=5))
    _drain(eng)
    assert (eng.stats["prefill_chunks"] > 0) == (n > 32)
    assert eng.stats["moe_pairs"] > 0
    return [(seq.prompt, seq.tokens)]


CASES = {
    "prompt_shorter_than_a_block": ("llama", lambda m: _whole_prompt(m, 5)),
    "prompt_a_block_multiple": ("llama", lambda m: _whole_prompt(m, 16)),
    "prompt_chunked": ("llama", _chunked),
    "chunk_and_decode_row_in_one_step": ("llama", _staggered),
    "prefix_hit": ("llama", lambda m: _prefix_hit(m, 6)),
    "prefix_hit_chunked_suffix": ("llama", lambda m: _prefix_hit(m, 40)),
    "evicted_and_restored": ("llama", _displaced),
    "decode_chunk_1": ("llama", lambda m: _fused_tail(m, 1)),
    "decode_chunk_8": ("llama", lambda m: _fused_tail(m, 8)),
    "olmoe_short_prompt": ("olmoe", lambda m: _olmoe(m, 11)),
    "olmoe_chunked_prompt": ("olmoe", lambda m: _olmoe(m, 70)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_stream_matches_forward(case, request):
    fixture, drive = CASES[case]
    model = request.getfixturevalue(fixture)
    pairs = drive(model)
    assert pairs and all(len(tokens) >= 5 for _, tokens in pairs)
    for prompt, tokens in pairs:
        served_equals_forward(model, prompt, tokens)


def test_oracle_refuses_a_wrong_token(llama):
    """The oracle has teeth: one served token replaced fails it."""
    (prompt, tokens), = _whole_prompt(llama, 5)
    wrong = list(tokens)
    wrong[2] = (wrong[2] + 1) % 256
    with pytest.raises(AssertionError, match="served token 2"):
        served_equals_forward(llama, prompt, wrong)


# ------------------------------------------------ scheduling is not sampling
SYSTEM = _prompt(20, 20)


def _workload():
    """Three families of prompts behind one system prompt, a greedy and a
    seeded-sampled request in each round, one prompt long enough to chunk:
    enough repeats for hits, evictions and (with a tier) readmissions."""
    reqs = []
    for rnd in range(2):
        for fam in range(3):
            tail = _prompt(30 + fam, 40 if fam == 2 else 9)
            kw = (dict(temperature=0.8, top_k=5, seed=100 + fam)
                  if (fam + rnd) % 2 else {})
            reqs.append(GenerationRequest(
                np.concatenate([SYSTEM, tail]), max_new_tokens=6, **kw))
    return reqs


def _streams(model, **kw):
    eng = _engine(model, **kw)
    traced = eng.decode_compilations()      # engines share the jit cache
    outs = [o.tolist() for o in eng.generate(_workload())]
    assert eng.decode_compilations() - traced <= 2      # a packed size each
    return outs, eng


POLICIES = {
    # knob: (settings a, settings b, what b must have exercised)
    "prefix_cache": (dict(prefix_cache=False), dict(prefix_cache=True),
                     lambda e: e.prefix_cache.stats["hits"] >= 3),
    "prefill_chunk": (dict(prefill_chunk=None), dict(prefill_chunk=32),
                      lambda e: e.stats["prefill_chunks"] >= 2),
    "num_slots": (dict(num_slots=2), dict(num_slots=4),
                  lambda e: e.stats["steps"] > 0),
    "host_tier_bytes": (dict(prefix_cache=True, prefix_blocks=2),
                        dict(prefix_cache=True, prefix_blocks=2,
                             host_tier_bytes=1 << 20),
                        lambda e: e.prefix_cache.stats[
                            "readmitted_blocks"] > 0),
}


@pytest.mark.parametrize("knob", sorted(POLICIES))
def test_stream_invariant_under_policy(knob, llama):
    a, b, exercised = POLICIES[knob]
    want, _ = _streams(llama, **a)
    got, eng = _streams(llama, **b)
    assert got == want
    assert exercised(eng)
    sampled = [r.temperature > 0 for r in _workload()]
    assert any(sampled) and not all(sampled)


# ------------------------------------------------------- the surface is shut
@pytest.mark.parametrize("name", ["paged_attn", "ragged_step", "fused_tick"])
def test_removed_switches_are_gone(name, capsys):
    from paddle_tpu.serving.fleet import EngineFleet
    from paddle_tpu.serving.server import serve, serve_fleet
    from paddle_tpu.serving.server.__main__ import main
    for fn in (ContinuousBatchingEngine.__init__, serve, serve_fleet,
               EngineFleet.__init__):
        assert name not in inspect.signature(fn).parameters, fn
    for flag in ("--" + name.replace("_", "-"),
                 "--no-" + name.replace("_", "-")):
        with pytest.raises(SystemExit) as e:
            main(["--preset", "tiny", flag])
        assert e.value.code == 2
        assert "unrecognized arguments: " + flag in capsys.readouterr().err
