"""The unified step's packed buffer follows the plan (ISSUE 34).

A step whose plan holds no prefill chunk runs the step program at
``num_slots`` rows (rounded up to 8); a step that carries a chunk runs it at
``num_slots + prefill_chunk``. One body, two shapes, each compiled when a
step first needs it; what one program hands the next (``tok_fin``, the keys,
the pool, a hybrid model's state store, the routing record) is by slot or
pool-shaped, so either size may be dispatched behind the other.

What that must not change: the streams. Dead rows contribute nothing, so an
engine of two sizes serves what the engine of one size served (the parent:
forced here by ``_decode_rows = _token_budget``) and what an unchunked
engine serves. What it adds, each pinned here: ``packed_rows`` on the
``dispatch`` span and ``serving_step_programs_total{rows}``; at most two
programs per ``n_steps``; and the chunk grant's decode baseline is fed only
by the program that carries chunks, so the unified step grants the cap.
"""
import collections
import json
import urllib.request

import numpy as np
import pytest

from paddle_tpu.profiler.tracing import SpanTracer
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.faults import VirtualClock
from paddle_tpu.serving.server import serve

import serving_support
from serving_support import BS, CHUNK

SLOTS = 3       # so the decode-only size is 8 rows, the other 3 + 16
SMALL, LARGE = 8, SLOTS + CHUNK

#: family: the seed its sibling files build it with
FAMILIES = {"llama": 28, "olmoe": 7, "deepseek_v2": 11, "olmo_hybrid": 7}


def _build(family, attention="jnp", build=serving_support.model):
    return build(family, seed=FAMILIES[family], decode_attention=attention)


@pytest.fixture(scope="module")
def models():
    """One model a family (the process's: ``serving_support.model``)."""
    return _build


def _engine(model, single_size=False, **kw):
    """The shared helper at three slots; ``single_size`` makes the parent's
    engine, whose every step runs at the token budget."""
    eng = serving_support.engine(model, **{"num_slots": SLOTS, **kw})
    if single_size:
        eng._decode_rows = eng._token_budget
    return eng


def _prompt(seed, n):
    return serving_support.prompt(seed, n, low=1)


def _script(sampled):
    """(step at which it arrives, request): a short prompt decoding alone
    (small programs), a long one arriving while a small program is in
    flight (large behind small), its last chunk handing over to decode rows
    (small behind large), a second long one, and a late short one."""
    kw = (lambda i: dict(temperature=0.8, top_k=5, seed=100 + i)) if sampled \
        else (lambda i: {})
    return [(0, GenerationRequest(_prompt(1, 9), max_new_tokens=14, **kw(0))),
            (3, GenerationRequest(_prompt(2, 40), max_new_tokens=6, **kw(1))),
            (4, GenerationRequest(_prompt(3, 21), max_new_tokens=5, **kw(2))),
            (14, GenerationRequest(_prompt(4, 11), max_new_tokens=4,
                                   **kw(3)))]


def _drive(eng, script, after_step=None):
    """Submit each request at its step, run dry; the sequences in order."""
    script = sorted(script, key=lambda e: e[0])
    seqs, i = [], 0
    while script or eng.has_work():
        while script and script[0][0] <= i:
            seqs.append(eng.submit(script.pop(0)[1]))
        eng.step()
        if after_step is not None:
            after_step(eng)
        i += 1
        assert i < 2000
    return seqs


def _dispatches(tracer):
    return [e["args"] for e in tracer.events() if e["name"] == "dispatch"]


def _size_changes_in_flight(disp):
    """(previous size, size) of every program dispatched behind a program
    of the other size."""
    return {(a["packed_rows"], b["packed_rows"])
            for a, b in zip(disp, disp[1:])
            if b["ahead"] and a["packed_rows"] != b["packed_rows"]}


# --------------------------------------------------- (a) the streams stand
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_streams_equal_the_single_size_and_the_unchunked_engine(
        family, sampled, models):
    model = models(family)
    tr = SpanTracer(clock=VirtualClock()).enable()
    eng = _engine(model)
    eng.tracer = tr
    got = [list(s.tokens) for s in _drive(eng, _script(sampled))]
    assert [len(t) for t in got] == [14, 6, 5, 4]
    parent = _drive(_engine(model, single_size=True), _script(sampled))
    assert got == [list(s.tokens) for s in parent]
    whole = _drive(_engine(model, prefill_chunk=None), _script(sampled))
    assert got == [list(s.tokens) for s in whole]
    # the size changed while a program was in flight, in both directions
    assert _size_changes_in_flight(_dispatches(tr)) \
        == {(SMALL, LARGE), (LARGE, SMALL)}


# ------------------------------- (b) packed_rows, the counter, two programs
@pytest.mark.parametrize("family,decode_chunk", [
    ("llama", 1), ("llama", 4), ("olmoe", 1), ("deepseek_v2", 1),
    ("olmo_hybrid", 1)])
def test_packed_rows_follow_the_plan_under_a_random_mix(
        family, decode_chunk, models):
    model = models(family)
    rng = np.random.RandomState(34)
    script = [(int(rng.randint(0, 40)), GenerationRequest(
        _prompt(50 + i, int(rng.randint(3, 60))),
        max_new_tokens=int(rng.randint(1, 12)),
        **(dict(temperature=0.7, top_k=4, seed=i) if i % 3 == 0 else {})))
        for i in range(12)]
    tr = SpanTracer(clock=VirtualClock()).enable()
    # programs of its own (on the jnp path, the cheapest there are): the
    # assertions are on what THIS engine has built, and when
    eng = _engine(model, jit_cache={}, decode_chunk=decode_chunk)
    eng.tracer = tr
    fenced = []
    eng.on_step = fenced.append
    assert eng.step_rows == (SMALL, LARGE) and eng.decode_compilations() == 0
    seqs = _drive(eng, script)
    assert all(s.done for s in seqs)
    disp = _dispatches(tr)
    assert len(disp) == eng.stats["unified_steps"] > 20
    for a in disp:
        assert a["packed_rows"] == (LARGE if a["prefill_tokens"] else SMALL)
        assert a["decode_rows"] <= SLOTS
    by_size = collections.Counter(a["packed_rows"] for a in disp)
    # every program dispatched was fenced, and counted under its own size
    assert {r: eng.stats["step_programs_%d" % r] for r in eng.step_rows} \
        == dict(by_size)
    assert sum(by_size.values()) == len(fenced)
    # one trace a (packed size reached, n_steps), never more than two sizes
    programs = collections.Counter(
        key[4] for key, fn in eng._jit.items()
        if key[0] == "ragged" for _ in range(fn._cache_size()))
    assert sum(programs.values()) == eng.decode_compilations()
    assert programs[1] == 2 and max(programs.values()) <= 2
    assert set(programs) <= {1, 2, 4} and (decode_chunk > 1) == (
        len(programs) > 1)
    for key in eng._jit:
        if key[0] == "ragged":
            assert key[1:4] in ((SLOTS, LARGE, SMALL), (SLOTS, LARGE, LARGE))


@pytest.mark.parametrize("traffic,sizes", [
    ("whole_prompts_only", (SMALL,)), ("chunks_then_decode", (LARGE, SMALL))])
def test_no_program_is_built_before_a_step_needs_it(traffic, sizes, models):
    """A mix whose prompts are all prefilled whole never builds the large
    program; one long request builds both, its chunk steps' first."""
    eng = _engine(models("llama"), jit_cache={})      # as above
    n = 9 if traffic == "whole_prompts_only" else 40
    for i in range(3):
        eng.generate([GenerationRequest(_prompt(70 + i, n + i),
                                        max_new_tokens=5)])
    built = [key[3] for key in eng._jit if key[0] == "ragged"]
    assert tuple(built) == sizes
    assert eng.decode_compilations() == len(sizes)
    assert [eng.stats["step_programs_%d" % r] > 0 for r in (SMALL, LARGE)] \
        == [SMALL in sizes, LARGE in sizes]


@pytest.mark.parametrize("kw,rows", [
    (dict(prefill_chunk=None), (SMALL,)),
    (dict(prefill_chunk=CHUNK, max_seq_len=16), (SMALL,)),
    (dict(num_slots=8), (8, 8 + CHUNK)),
    (dict(num_slots=9), (16, 9 + CHUNK)),
    (dict(decode_ticks=2), (LARGE,)),
    (dict(spec_decode=True, spec_k=2), (LARGE,)),
], ids=["unchunked", "chunk_never_reached", "eight_slots", "nine_slots",
        "multi_tick", "speculative"])
def test_the_sizes_an_engine_can_reach(kw, rows, models):
    """``step_rows``: the slots' rows rounded up to 8 and, where a prompt
    can be chunked, the token budget; the multi-tick and the speculative
    step keep their one size."""
    eng = _engine(models("llama"), jit_cache={}, **kw)    # as above
    assert eng.step_rows == rows
    assert all(eng.stats["step_programs_%d" % r] == 0 for r in rows)
    eng.generate([GenerationRequest(_prompt(80, 7), max_new_tokens=6)])
    assert sum(eng.stats["step_programs_%d" % r] for r in rows) >= 2
    assert eng.decode_compilations() == 1


def test_metrics_count_the_programs_by_size(models):
    srv = serve(models("llama"), port=0, num_slots=SLOTS, max_seq_len=96,
                prefill_chunk=CHUNK, prefix_block_size=BS)
    try:
        for n in (9, 40):
            req = urllib.request.Request(
                srv.url + "/v1/completions",
                data=json.dumps({"prompt": _prompt(90 + n, n).tolist(),
                                 "max_tokens": 5}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert len(json.loads(resp.read())["choices"][0]["token_ids"]
                           ) == 5
        assert srv.gateway.shutdown(drain=True, timeout=60)
        text = srv.gateway.registry.render()
    finally:
        srv.shutdown()
    values = {}
    for line in text.splitlines():
        if line.startswith(("serving_step_programs_total{",
                            "serving_step_duration_seconds_count")):
            name, value = line.rsplit(" ", 1)
            values[name] = float(value)
    small = values['serving_step_programs_total{rows="%d"}' % SMALL]
    large = values['serving_step_programs_total{rows="%d"}' % LARGE]
    assert small >= 8 and large == 3        # 4 + 4 decode steps; 3 chunks
    assert small + large == values["serving_step_duration_seconds_count"]


# ------------------------- (c) the chunk grant does not compare two programs
def _cost(fl):
    """The batch cell's step at toy size: the decode-only program 10.5 ms,
    the chunk-carrying one 27.2 ms and 27 us a token."""
    return 0.0105 if fl.size == SMALL else 0.0272 + 0.000027 * fl.packed


def test_grant_stays_at_the_cap_through_a_long_all_chunk_run(models):
    """Closed loop, two clients, prompts of 24-40 tokens and 3 tokens out:
    most steps carry a chunk and those that do not run the small
    program, whose 10.5 ms say nothing of the large program's 27 ms floor. A
    grant reckoned across the two (``afford = tps x 2 x 10.5 ms``, under the
    cap) decays step by step; the baseline therefore stays unfed and the
    grant at the cap."""
    clock = VirtualClock()
    eng = _engine(models("llama"), step_clock=clock, headroom_mult=2.0)
    rng = np.random.RandomState(5)
    live, done, steps, small_steps, grants = [], 0, 0, 0, []
    while steps < 500:
        live = [s for s in live if not s.done]
        while len(live) < 2:
            live.append(eng.submit(GenerationRequest(
                _prompt(200 + done, int(rng.randint(24, 41))),
                max_new_tokens=3)))
            done += 1
        eng.step()
        steps += 1
        fl = eng._inflight
        if fl is not None:
            clock.advance(_cost(fl))
            small_steps += fl.size == SMALL
            if fl.chunks:
                grants.append(eng.stats["headroom"])
        assert eng._dt_decode_ewma is None
    assert small_steps >= 5 and len(grants) >= 300, (small_steps, len(grants))
    assert eng._tps_ewma > 500            # the chunk steps are measured
    assert set(grants) == {CHUNK}, collections.Counter(grants)
    assert eng._prefill_budget() == CHUNK
    # the chunk steps moved the cap's worth of tokens
    assert eng.stats["step_prefill_tokens"] >= 0.75 * CHUNK * len(grants)


@pytest.mark.parametrize("engine_kw,fed", [
    (dict(), False), (dict(decode_ticks=2), True),
    (dict(spec_decode=True, spec_k=2), True)],
    ids=["unified", "multi_tick", "speculative"])
def test_only_the_program_that_carries_chunks_feeds_the_baseline(
        engine_kw, fed, models):
    """Decode-only steps of the unified engine leave ``_dt_decode_ewma`` as
    it was; the multi-tick and the speculative step have one program, which
    carries the chunks too, and keep feeding it."""
    clock = VirtualClock()

    class Ticking:
        def __call__(self):
            clock.advance(0.004)
            return clock()
    eng = _engine(models("llama"), step_clock=Ticking(), **engine_kw)
    eng._dt_decode_ewma, eng._tps_ewma = 0.123, 4000.0
    eng.generate([GenerationRequest(_prompt(300, 9), max_new_tokens=12)])
    assert eng.stats["prefill_chunks"] == 0
    if fed:
        assert eng._dt_decode_ewma < 0.1
    else:
        assert eng._dt_decode_ewma == 0.123
        assert eng.stats["step_programs_%d" % SMALL] == 11


# ------------- (d) the state store and the routing record across a change
def test_the_state_store_survives_a_size_change_in_both_directions():
    """Olmo-Hybrid with its two kernels (interpret mode): after every step
    the store of the engine of two sizes equals the single-size engine's,
    through small -> large -> small, and so do the streams."""
    seen = []

    def record(eng):
        seen.append(tuple(np.asarray(a) for a in eng.cache.state))
    tr = SpanTracer(clock=VirtualClock()).enable()
    model = _build("olmo_hybrid", "pallas")
    eng = _engine(model)
    eng.tracer = tr
    got = _drive(eng, _script(False), after_step=record)
    two, seen = seen, []
    want = _drive(_engine(model, single_size=True), _script(False),
                  after_step=record)
    assert [s.tokens for s in got] == [s.tokens for s in want]
    assert len(two) == len(seen) > 15
    for step, (a, b) in enumerate(zip(two, seen)):
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6,
                                       err_msg=f"step {step}")
    disp = _dispatches(tr)
    assert _size_changes_in_flight(disp) == {(SMALL, LARGE), (LARGE, SMALL)}
    # every span's state row is counted once, whatever its program's size
    # (and the two prompts prefilled whole)
    assert eng.stats["state_rows"] == sum(
        a["state_rows"] for a in disp) + 2


def test_the_routing_record_survives_a_size_change_in_both_directions():
    """DeepSeek-V2's served picks, noted by program and row: read back by
    position they are the single-size engine's, chunks (large program) and
    decode rows (small program) alike."""
    picks = []
    for single in (False, True):
        # a model of its own: the routing record is written on it
        model = _build("deepseek_v2", build=serving_support.fresh_model)
        seqs = _drive(_engine(model, single_size=single), _script(False))
        ids = [np.concatenate([s.prompt, np.asarray(s.tokens, np.int32)])
               for s in seqs]
        picks.append([model.served_router_picks(i[None]) for i in ids])
        for p, i, s in zip(picks[-1], ids, seqs):
            ran = i.size - 1            # the last token is never fed back
            assert (p[:, :, :ran] >= 0).all() and (p[:, :, ran:] == -1).all()
    for a, b in zip(*picks):
        assert np.array_equal(a, b)
