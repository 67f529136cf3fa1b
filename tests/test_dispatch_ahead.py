"""The unified step is dispatched one program ahead (ISSUE 30).

One ``engine.step()`` plans and dispatches program j and only then fences
program j-1 and accepts its tokens; a decode row of j takes its input token
from j-1's output on the device. What that must not change: greedy streams
are still the forward pass's (``served_equals_forward`` of
``tests/test_serving_oracle.py``), sampled streams depend on their own
prompt and key only. What it adds, each pinned here: an EOS is found one
program late and the extra token never surfaces; a token is accepted only
for the sequence it was computed for; a ``length`` finish is known ahead and
wastes no row; every path that changes slots outside plan -> accept drains
first; a final chunk hands its token 0 to the next program's decode row on
the device; the routing counts on a ``device-wait`` are those of the step
it fenced; the order dispatch j before read j-1 holds with no chip; and
there is one program a packed size whether or not anything is in flight.
"""
import numpy as np
import pytest

from paddle_tpu.profiler.tracing import SpanTracer
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.faults import FaultPlan, VirtualClock
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from serving_support import drain as _run, engine as _engine
from test_serving_oracle import served_equals_forward


@pytest.fixture(scope="module")
def llama():
    return serving_support.model("llama", seed=28)


@pytest.fixture(scope="module")
def olmoe():
    return serving_support.model("olmoe", seed=7)


def _prompt(seed, n):
    return serving_support.prompt(seed, n, low=1)


def _alone(model, request, **kw):
    """The stream a request gets on an engine of its own."""
    eng = _engine(model, **kw)
    seq = eng.submit(request)
    _run(eng)
    return list(seq.tokens)


class _Logged:
    """A program output that says when the host reads it: ``np.asarray``
    logs, everything else (``.at``, passing it to the next program) is
    the device's business and passes through."""

    def __init__(self, real, log, what):
        self.__dict__.update(_real=real, _log=log, _what=what)

    def __array__(self, *args, **kwargs):
        self._log.append(self._what)
        return np.asarray(self._real, *args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._real, name)


def spy(eng):
    """Log ``("dispatch", j, {...})`` when the engine calls its step
    program and ``("read", j, output)`` when the host first touches one of
    its outputs (tokens, next-step tokens, keys, routing summary)."""
    log, real_fn, count = [], eng._ragged_fn, [0]

    def ragged_fn(n, rows):
        fn = real_fn(n, rows)

        def call(*args):
            j = count[0]
            count[0] += 1
            args = [a._real if isinstance(a, _Logged) else a for a in args]
            dec_mask, take = args[10], args[15]
            fl = eng._inflight
            log.append(("dispatch", j, {
                "rows": int(dec_mask.sum()), "take": take.copy(),
                "final_chunk_in_flight": [
                    slot for slot, _s, _o, _n, final
                    in (fl.chunks if fl is not None else ()) if final]}))
            out = list(fn(*args))
            for i, name in ((2, "toks"), (3, "tok_fin"), (4, "keys")):
                out[i] = _Logged(out[i], log, ("read", j, name))
            for i in range(5, len(out)):
                out[i] = _Logged(out[i], log, ("read", j, "moe"))
            return tuple(out)
        return call

    eng._ragged_fn = ragged_fn
    return log


def _dispatches(log):
    return [e for e in log if e[0] == "dispatch"]


def _emitting_eos(model, request_kw, **engine_kw):
    """A greedy stream and a token of it that first shows at index >= 2:
    as ``eos_token_id`` it is found by a decode step, with the next
    program already dispatched."""
    free = _alone(model, GenerationRequest(**request_kw), **engine_kw)
    for k in range(2, len(free) - 1):
        if free[k] not in free[:k]:
            return free, k
    raise AssertionError(f"no late first occurrence in {free}")


# ------------------------------------------------------------ (a) late EOS
def test_eos_found_one_step_late_never_surfaces(llama):
    kw = dict(prompt=_prompt(40, 9), max_new_tokens=24)
    free, k = _emitting_eos(llama, kw)
    eng = _engine(llama)
    log = spy(eng)
    pool_free = eng.cache.pool.num_free
    seen = []
    eng.on_token = lambda seq, tok: seen.append(tok)
    seq = eng.submit(GenerationRequest(eos_token_id=free[k], **kw))
    _run(eng)
    assert seq.tokens == free[:k + 1] == seen
    assert seq.finish_reason == "stop"
    served_equals_forward(llama, seq.prompt, seq.tokens)
    # token 0 came with the prefill; k decode tokens were accepted and one
    # more row had been dispatched before the EOS reached the host
    rows = sum(d[2]["rows"] for d in _dispatches(log))
    assert rows == k + 1
    assert eng.stats["tokens_generated"] == k + 1
    # nothing of the extra program stays: slot, blocks and lengths
    assert eng.cache.num_free == eng.num_slots
    assert eng.cache.pool.num_free == pool_free
    assert int(eng.cache.lengths.sum()) == 0
    assert eng._inflight is None and not eng.has_work()


# -------------------------------------------------- (b) accept by identity
def test_refilled_slot_gets_no_token_of_the_old_row(llama):
    kw = dict(prompt=_prompt(41, 7), max_new_tokens=24)
    free, k = _emitting_eos(llama, kw, num_slots=1)
    other = GenerationRequest(_prompt(42, 11), max_new_tokens=8,
                              temperature=0.8, top_k=5, seed=7)
    want = _alone(llama, other, num_slots=1)
    eng = _engine(llama, num_slots=1)
    old = eng.submit(GenerationRequest(eos_token_id=free[k], **kw))
    new = eng.submit(other)
    while not old.done:
        eng.step()
    # the program carrying the old row's extra token is still in flight
    # when the very next step gives the slot to the waiting sequence
    assert eng._inflight is not None
    assert [s for s, _ in eng._inflight.rows] == [0]
    eng.step()
    assert new.slot == 0 and new.status == "running"
    assert len(new.tokens) == 1         # its own token 0, nothing else
    _run(eng)
    assert old.tokens == free[:k + 1]
    assert new.tokens == want and len(want) == 8


# ------------------------------------------------ (c) length wastes no row
def test_length_finishes_waste_no_row(llama):
    eng = _engine(llama, num_slots=3)
    log = spy(eng)
    seqs = [eng.submit(GenerationRequest(_prompt(50 + i, n),
                                         max_new_tokens=m))
            for i, (n, m) in enumerate(((5, 1), (9, 2), (12, 7), (40, 5),
                                        (6, 11)))]
    _run(eng)
    assert all(s.finish_reason == "length" for s in seqs)
    # every token but a sequence's first (prefill or final chunk) is one
    # decode row, and no row was dispatched for a token never accepted
    rows = sum(d[2]["rows"] for d in _dispatches(log))
    assert rows == sum(len(s.tokens) - 1 for s in seqs) == 21
    assert eng.stats["steps_dispatched_ahead"] >= len(_dispatches(log)) - 2
    for s in seqs:
        served_equals_forward(llama, s.prompt, s.tokens)


# ------------------------------------- (d) what changes slots drains first
def _pair():
    return [GenerationRequest(_prompt(60, 9), max_new_tokens=14),
            GenerationRequest(_prompt(61, 13), max_new_tokens=14,
                              temperature=0.9, top_k=5, seed=123)]


def _in_flight_after(eng, seqs, tokens):
    while min(len(s.tokens) for s in seqs) < tokens:
        eng.step()
    assert eng._inflight is not None
    return eng


def test_cancel_with_a_step_in_flight(llama):
    want = [_alone(llama, r) for r in _pair()]
    eng = _engine(llama)
    seqs = [eng.submit(r) for r in _pair()]
    _in_flight_after(eng, seqs, 4)
    assert eng.cancel(seqs[0])
    assert eng.stats["drains_cancel"] == 1 and eng._inflight is None
    # the drain accepted the token in flight before the teardown
    assert seqs[0].finish_reason == "cancelled"
    assert 4 < len(seqs[0].tokens) < 14
    assert seqs[0].tokens == want[0][:len(seqs[0].tokens)]
    _run(eng)
    assert seqs[1].tokens == want[1]
    assert eng.cache.num_free == eng.num_slots


def test_evict_and_restore_with_a_step_in_flight(llama):
    want = [_alone(llama, r) for r in _pair()]
    eng = _engine(llama)
    seqs = [eng.submit(r) for r in _pair()]
    _in_flight_after(eng, seqs, 5)
    # the sampled one: its key snapshot must be the accepted tokens' key
    assert eng.evict(seqs[1]) and seqs[1].slot is None
    assert eng.stats["drains_evict"] == 1
    assert eng.restore(seqs[1])
    _run(eng)
    assert [s.tokens for s in seqs] == want
    assert eng.stats["restores"] == 1


def test_pool_exhausted_repair_with_a_step_in_flight(llama):
    want = [_alone(llama, r) for r in _pair()]
    eng = _engine(llama, prefix_cache=True)
    FaultPlan().at_step(5, "pool").install(eng)
    seqs = [eng.submit(r) for r in _pair()]
    _run(eng)
    assert eng.stats["drains_pool"] == 1
    assert eng.stats["preemptions"] == 1 and eng.stats["restores"] == 1
    assert [s.tokens for s in seqs] == want
    assert eng.cache.num_free == eng.num_slots


def test_gateway_rebuild_with_a_step_in_flight(llama):
    want = [_alone(llama, r) for r in _pair()]
    clk = VirtualClock()
    def factory():
        return _engine(llama, step_clock=clk)

    first = factory()
    gw = ServingGateway(first, engine_factory=factory,
                        fault_hook=FaultPlan(clock=clk).at_step(5, "fatal"),
                        clock=clk, retry_backoff_s=0.0, max_restarts=4,
                        start=False)
    streams = [gw.submit(r) for r in _pair()]
    gw.start()
    outs = [s.result() for s in streams]
    assert gw.shutdown(drain=True, timeout=60)
    assert [list(toks) for toks, _reason in outs] == want
    assert gw.restarts == 1 and gw.engine is not first
    # the dying engine's program in flight was fenced and accepted for the
    # snapshot; the counter survives the rebuild
    assert first.stats["drains_snapshot"] == 1 and first._inflight is None
    assert gw._stat("drains_snapshot") == 1
    assert gw._stat("steps_dispatched_ahead") > first.stats[
        "steps_dispatched_ahead"] > 0


class _Broken:
    """A program output whose host read fails as a lost device does."""

    def __array__(self, *a, **kw):
        raise RuntimeError("device lost")


def _by_hand(gw):
    """One pass of the driver's loop, on the test's thread."""
    gw._admit_intake()
    gw._apply_cancels()
    gw._apply_migrate_out()
    if gw.engine.has_work():
        gw._step_supervised()


@pytest.mark.parametrize("how", ["cancel", "migrate_out"])
def test_fence_that_raises_at_a_drain_between_steps_is_supervised(llama,
                                                                  how):
    """A client's disconnect (or a migration) meets the program in flight
    between two steps, outside ``step()``. A device fault that surfaces at
    that fence takes the supervisor's path (classify, rebuild, recover),
    not the driver's death: the other streams complete as they would have,
    and the request that asked is still honoured."""
    reqs = _pair() + [GenerationRequest(_prompt(63, 11), max_new_tokens=14)]
    want = [_alone(llama, r, num_slots=3) for r in reqs]
    def factory():
        return _engine(llama, num_slots=3)

    first = factory()
    gw = ServingGateway(first, engine_factory=factory, retry_backoff_s=0.0,
                        max_restarts=4, start=False)
    streams = [gw.submit(r) for r in reqs]
    while not all(s.seq is not None and len(s.seq.tokens) >= 4
                  for s in streams):
        _by_hand(gw)
    assert first._inflight is not None
    first._inflight.toks = _Broken()
    handed = []
    if how == "cancel":
        streams[0].cancel()
        gw._apply_cancels()             # the fence raises in here
    else:
        gw._migrate_out.append(
            (streams[0], lambda stream, seq: handed.append(seq)))
        gw._apply_migrate_out()
    assert gw.restarts == 1 and gw.engine is not first
    assert first.stats["drains_fault"] == 1 and first._inflight is None
    assert not any(s.finish_reason for s in streams)    # nobody stranded
    while gw.engine.has_work() or gw._migrate_out:
        _by_hand(gw)
    outs = [s.result() for s in streams[1:]]
    assert [list(toks) for toks, _reason in outs] == want[1:]
    if how == "cancel":
        toks, reason = streams[0].result()
        assert reason == "cancelled"
        assert list(toks) == want[0][:len(toks)] and len(toks) >= 4
    else:                   # evicted from the rebuilt engine, handed over
        assert handed == [streams[0].seq] and not handed[0].done
        assert handed[0].tokens == want[0][:len(handed[0].tokens)]
    # every fenced program was observed once, wherever it was fenced
    s = gw.registry.render()
    count = next(float(line.split()[-1]) for line in s.splitlines()
                 if line.startswith("serving_step_duration_seconds_count"))
    drains = sum(gw._stat("drains_" + r) for r in
                 ("idle", "cancel", "evict", "preempt", "pool", "deadline",
                  "snapshot"))
    assert count == gw._stat("steps_dispatched_ahead") + drains
    assert gw._stat("drains_fault") == 1 and gw._stat("drains_" + (
        "cancel" if how == "cancel" else "evict")) == 0
    gw.shutdown(drain=False, timeout=10)


def test_fence_that_raises_drops_what_was_in_flight(llama):
    """A device error surfaces at the fence, inside ``step()``. Nothing of
    the failed program or of the one dispatched behind it is accepted;
    keys and chunk offsets go back to what was accepted, so the same engine
    (the supervisor's transient retry) serves the same streams."""
    reqs = _pair() + [GenerationRequest(_prompt(62, 50), max_new_tokens=5)]
    want = [_alone(llama, r, num_slots=3) for r in reqs]
    eng = _engine(llama, num_slots=3)
    real_fn, count = eng._ragged_fn, [0]

    class Broken:
        def __init__(self, real):
            self.real = real

        def __array__(self, *a, **kw):
            raise RuntimeError("device lost")

    def ragged_fn(n, rows):
        fn = real_fn(n, rows)

        def call(*args):
            out = list(fn(*args))
            count[0] += 1
            if count[0] == 3:       # chunks and decode rows are in it
                out[2] = Broken(out[2])
            return tuple(out)
        return call

    eng._ragged_fn = ragged_fn
    seqs = [eng.submit(r) for r in reqs]
    faults = 0
    while eng.has_work():
        try:
            eng.step()
        except RuntimeError:
            faults += 1
            assert eng._inflight is None
            assert seqs[2].status == "prefilling"
            assert seqs[2].prefilled == int(eng.cache.lengths[seqs[2].slot])
    assert faults == 1 and eng.stats["drains_fault"] == 1
    assert [s.tokens for s in seqs] == want


def test_deadline_with_a_step_in_flight(llama):
    want = _alone(llama, _pair()[1])
    eng = _engine(llama)
    slow = eng.submit(GenerationRequest(_prompt(60, 9), max_new_tokens=40,
                                        timeout_s=3600.0))
    other = eng.submit(_pair()[1])
    _in_flight_after(eng, [slow, other], 3)
    slow.deadline = 0.0                 # long past
    done = eng.step()
    assert slow in done and slow.finish_reason == "timeout"
    assert eng.stats["drains_deadline"] == 1
    _run(eng)
    assert other.tokens == want


# ------------------------------- (e) a final chunk hands over its token 0
def test_final_chunk_hands_token_0_to_the_next_decode_row(llama):
    eng = _engine(llama)
    log = spy(eng)
    seq = eng.submit(GenerationRequest(_prompt(3, 50), max_new_tokens=6))
    _run(eng)
    assert eng.stats["prefill_chunks"] == 4         # ceil(50 / 16)
    served_equals_forward(llama, seq.prompt, seq.tokens)
    disp = _dispatches(log)
    # chunks ride the pipeline: every program after the first went behind
    # another one, and the one behind the final chunk carries the decode
    # row whose input token the host has not seen
    assert eng.stats["steps_dispatched_ahead"] == len(disp) - 1
    after_final = [d[2] for d in disp if d[2]["final_chunk_in_flight"]]
    assert len(after_final) == 1
    assert after_final[0]["rows"] == 1
    assert after_final[0]["take"][seq.slot or 0] == 1
    # a restored sequence's final chunk adopts no token: its decode row
    # reads the last streamed token from the host, and the stream goes on
    eng2 = _engine(llama)
    log2 = spy(eng2)
    long = eng2.submit(GenerationRequest(_prompt(4, 40), max_new_tokens=9,
                                         temperature=0.7, top_k=4, seed=5))
    while len(long.tokens) < 4:
        eng2.step()
    assert eng2.evict(long) and eng2.restore(long)
    _run(eng2)
    assert long.tokens == _alone(llama, GenerationRequest(
        _prompt(4, 40), max_new_tokens=9, temperature=0.7, top_k=4, seed=5))
    after = [d[2] for d in _dispatches(log2) if d[2]["final_chunk_in_flight"]]
    assert [int(d["take"].sum()) for d in after] == [1, 0]


# ---------------------------------------------- (f) the routed-FFN model
def test_olmoe_streams_and_the_counts_of_the_step_fenced(olmoe):
    tr = SpanTracer(clock=VirtualClock()).enable()
    eng = _engine(olmoe, num_slots=3, prefill_chunk=32)
    eng.tracer = tr
    seqs = [eng.submit(GenerationRequest(_prompt(70 + i, n),
                                         max_new_tokens=m))
            for i, (n, m) in enumerate(((11, 7), (70, 5), (6, 9)))]
    _run(eng)
    for s in seqs:
        served_equals_forward(olmoe, s.prompt, s.tokens)
    c = olmoe.config
    per_token = c.num_experts_per_tok * c.num_hidden_layers
    evs = sorted((e for e in tr.events() if e["ph"] == "X"
                  and e["name"] in ("dispatch", "device-wait")),
                 key=lambda e: e["ts"])
    disp = [e["args"] for e in evs if e["name"] == "dispatch"]
    waits = [e["args"] for e in evs if e["name"] == "device-wait"]
    assert len(disp) == len(waits) == eng.stats["unified_steps"]
    # the k-th fence is the k-th program's: its live tokens, K picks each,
    # in every layer (the token counts differ from step to step here)
    assert len({d["decode_tokens"] + d["prefill_tokens"]
                for d in disp}) > 2
    for d, w in zip(disp, waits):
        assert w["moe_pairs"] \
            == (d["decode_tokens"] + d["prefill_tokens"]) * per_token
        assert w["moe_layer_calls"] == c.num_hidden_layers
    # and in time the k-th dispatch comes before the (k-1)-th fence
    names = [e["name"] for e in evs]
    assert names[:3] == ["dispatch", "dispatch", "device-wait"]
    assert sum(d["ahead"] for d in disp) \
        == eng.stats["steps_dispatched_ahead"] > len(disp) // 2


# ------------------------------------------------- (g) order, with no chip
def test_dispatch_j_precedes_the_first_read_of_j_minus_1(llama):
    eng = _engine(llama, num_slots=3)
    log = spy(eng)
    seqs = [eng.submit(GenerationRequest(_prompt(80 + i, 6 + i),
                                         max_new_tokens=16))
            for i in range(3)]
    _run(eng)
    for s in seqs:
        served_equals_forward(llama, s.prompt, s.tokens)
    order = [(e[0], e[1]) for e in log]
    first_read = {}
    for i, (kind, j) in enumerate(order):
        if kind == "read":
            first_read.setdefault(j, i)
    at = {j: i for i, (kind, j) in enumerate(order) if kind == "dispatch"}
    assert len(at) == 15            # a program a decode token of a row
    for j in range(1, len(at)):
        # steady decode: program j is on the queue before anything of
        # j-1 has been read, so nothing between plan j and dispatch j
        # fenced it
        assert at[j] < first_read[j - 1], (j, order)
    # the host reads tokens (and nothing else) of each program, once
    assert [e[2] for e in log if e[0] == "read"] == ["toks"] * len(at)


# --------------------------------------------------------- (h) one program
def test_one_program_with_and_without_a_drain(llama):
    eng = _engine(llama)
    eng.generate([GenerationRequest(_prompt(90, 20), max_new_tokens=2)])
    warm = eng.decode_compilations()
    assert warm == 2        # its chunk steps' size, its decode step's size
    eng.generate([GenerationRequest(_prompt(91, 9), max_new_tokens=64)])
    assert eng.stats["steps_dispatched_ahead"] >= 60
    assert eng.decode_compilations() == warm
    seq = eng.submit(GenerationRequest(_prompt(92, 9), max_new_tokens=64))
    victim = eng.submit(GenerationRequest(_prompt(93, 30),
                                          max_new_tokens=64))
    while len(seq.tokens) < 20:
        eng.step()
    assert eng._inflight is not None and eng.cancel(victim)
    _run(eng)
    assert len(seq.tokens) == 64 and eng.stats["drains_cancel"] == 1
    assert eng.decode_compilations() == warm
