"""The serving tests' support module (``serving_support``, ROADMAP D6): one
model a (architecture, config, seed) for the process, one program cache a
model and argument geometry, so that a step program is traced, lowered and
compiled once in a process and the compile pins read on the shared cache what
they read on a fresh one.

Everything here runs on the jnp attention path (the cheapest step program
there is to lower) and on a seed no other file asks for, so the counts below
are this file's own whichever files the worker ran before it.
"""
import pytest

import serving_support
from paddle_tpu.serving import GenerationRequest
from serving_support import BS, CHUNK, SLOTS, engine, programs, prompt

SEED = 46


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=SEED, decode_attention="jnp")


def _short():
    return [GenerationRequest(prompt=prompt(1, 9), max_new_tokens=3)]


def _chunked():
    return [GenerationRequest(prompt=prompt(2, 40), max_new_tokens=3)]


def test_one_model_a_configuration_and_seed(model):
    assert serving_support.model("llama", seed=SEED,
                                 decode_attention="jnp") is model
    assert serving_support.model("llama", seed=SEED + 1,
                                 decode_attention="jnp") is not model
    assert serving_support.model("llama", seed=SEED) is not model
    fresh = serving_support.fresh_model("llama", seed=SEED,
                                        decode_attention="jnp")
    assert fresh is not model
    # the weights are a function of (architecture, config, seed)
    a, _ = model.decode_params()
    b, _ = fresh.decode_params()
    assert (a["embed"] == b["embed"]).all()


def test_two_engines_of_one_geometry_share_programs(model):
    # (four slots: the counts are of programs no other test here builds)
    first = engine(model, num_slots=4)
    first.generate(_short())
    held = programs(model, max_seq_len=serving_support.S_MAX,
                    prefix_block_size=BS)
    assert first._jit is held
    keys = set(held)
    assert first.decode_compilations() == 1     # the decode-only size
    second = engine(model, num_slots=4)
    assert second._jit is held
    second.generate(_short())
    assert set(held) == keys                    # the second added nothing
    assert second.decode_compilations() == 1
    assert second.prefill_compilations() == first.prefill_compilations()


def test_variants_and_packed_sizes_key_apart_on_the_shared_cache(model):
    """What the 103 s and 61 s tests compiled six step programs with the
    interpreted kernels to say: one dict holds every variant, keyed apart,
    and each engine counts its own programs only."""
    engines = {
        (): engine(model),
        ("kv8",): engine(model, kv_dtype="int8"),
        ("kv8f",): engine(model, kv_dtype="fp8"),
        ("w8",): engine(model, quantize_weights=True),
    }
    for eng in engines.values():
        eng.generate(_chunked())        # both packed sizes
    (held,) = {id(e._jit): e._jit for e in engines.values()}.values()
    for tags, eng in engines.items():
        for rows in (SLOTS + CHUNK, 8):
            assert ("ragged", SLOTS, SLOTS + CHUNK, rows, 1, "jnp") + tags \
                in held
        assert eng.step_rows == (8, SLOTS + CHUNK)
        assert eng.decode_compilations() == 2, tags


@pytest.mark.parametrize("kw", [
    dict(max_seq_len=64), dict(prefix_block_size=16),
    dict(prefix_cache=True), dict(prefix_cache=True, prefix_blocks=4),
], ids=lambda kw: ",".join(sorted(kw)))
def test_a_geometry_that_shapes_the_arguments_has_its_own_programs(kw, model):
    """The engine's keys do not carry the pool's size or the tables' width:
    one jitted callable traced at two shapes would count two compilations."""
    base = engine(model)
    other = engine(model, **kw)
    assert other._jit is not base._jit
    assert engine(model, **kw)._jit is other._jit
    # what only chooses a program is in the engine's own keys: same dict
    assert engine(model, num_slots=3)._jit is base._jit
    assert engine(model, kv_dtype="int8", step_clock=lambda: 0.0)._jit \
        is base._jit


def test_a_shared_trie_keys_by_its_pool(model):
    donor = engine(model, prefix_cache=True)
    adopter = engine(model, prefix_cache=donor.prefix_cache)
    assert adopter.prefix_cache is donor.prefix_cache
    assert adopter._jit is engine(
        model, prefix_cache=donor.prefix_cache)._jit


def test_a_test_may_still_bring_its_own_programs(model):
    own = {}
    eng = engine(model, jit_cache=own)
    assert eng._jit is own


def test_prefill_programs_asked_counts_this_engine_only(model):
    eng = serving_support.watch_prefill_programs(engine(model))
    eng.generate(_chunked())            # chunks ride the unified step
    assert eng.prefill_programs_asked == 0
    eng.generate(_short())              # a whole-prompt prefill
    assert eng.prefill_programs_asked == 1


def test_wait_until_waits_on_progress_and_guards_a_hang():
    polls = []
    serving_support.wait_until(lambda: polls.append(1) or len(polls) >= 3)
    assert len(polls) == 3
    with pytest.raises(AssertionError, match="hung waiting for never"):
        serving_support.wait_until(lambda: False, "never", hang_s=0.05)


def test_helpers_give_the_requests_the_files_had():
    a, b = prompt(7, 12), prompt(7, 12)
    assert a.dtype.name == "int32" and (a == b).all() and len(a) == 12
    assert 0 not in serving_support.token_list(64, seed=3)
    r = GenerationRequest(prompt=a, max_new_tokens=5, temperature=0.8,
                          top_k=4, seed=9, eos_token_id=3)
    c = serving_support.clone(r)
    assert c is not r and (c.prompt == r.prompt).all()
    assert (c.max_new_tokens, c.temperature, c.top_k, c.seed,
            c.eos_token_id) == (5, 0.8, 4, 9, 3)
    assert serving_support.match_fraction([[1, 2, 3, 4]], [[1, 2, 9, 4]]) \
        == 0.5
    reqs = serving_support.mixed_reqs(sampled=True, n_reqs=3)
    assert [r.seed for r in reqs] == [500, 501, 502]
    assert (reqs[0].prompt[:24] == reqs[2].prompt[:24]).all()
