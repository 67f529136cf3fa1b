"""Multi-tick on-device decode (engine ``decode_ticks > 1``, README
"Multi-tick decode"): the unified ragged step's fused tail driven past
the host sync — one program runs up to n decode ticks with on-device
EOS/budget retirement, and the host accepts the whole token block in
one ``host-accept``. The load-bearing properties:

- **Transparency**: token streams are byte-identical to
  ``decode_ticks=1`` (and to the two-program baseline) — greedy AND
  seeded-sampled, across a mixed chunked/sampled/cancel matrix and
  under the chaos fault matrix — and ``decode_compilations()`` stays
  at 1 INCLUSIVE of the multi-tick geometry (the tick count is a
  runtime argument of one program).
- **Finish masking**: EOS on tick 0 / tick n-1, budget cuts mid-block,
  and all-slots-finish-early (the program returns with ticks to
  spare) all trim exactly where tick-at-a-time would stop, with the
  device's append cut equal to the host's trim (pool accounting
  restored exactly at retirement).
- **Scheduling**: the tick count adapts — clamped to 1 under mixed
  traffic, shrunk to the nearest guaranteed retirement while the
  queue waits — so admission latency and TTFT never regress.
- **Observability**: ``serving_decode_ticks_per_sync`` on /metrics,
  exact per-decoded-token dispatch attribution via the live
  ``serving_dispatches_per_decoded_token`` gauge, and the
  ``/debug/requests`` TPOT-so-far column derived from accepted-token
  stamps (no clock-inflated numerator mid-step).
"""
import itertools

import pytest

from paddle_tpu.serving import FIFOScheduler, GenerationRequest
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.server import ServingGateway

import serving_support
from serving_support import (clone as _clone, engine as _engine,
                             prompt as _prompt)
from test_metrics_prom import parse_prometheus

TICKS = 8


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 8)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


def _greedy_ref(model, n=24, seed=5):
    """A reference greedy stream, used to plant EOS tokens at exact
    tick offsets."""
    eng = _engine(model)
    out = eng.generate([GenerationRequest(prompt=_prompt(seed, 10),
                                          max_new_tokens=n)])[0]
    return out.tolist()


# ---------------------------------------------------------- transparency
class TestTransparency:
    @pytest.mark.slow  # 8 s matrix duplicate: test_multitick_equals_two_
    # program_baseline below keeps the default transparency rep (870s cap)
    def test_multitick_equals_single_tick_mixed_matrix(self, model):
        """The acceptance pin: a chunked/sampled/cancel traffic matrix
        — varied prompt lengths, greedy and seeded-sampled rows, a
        long prompt that chunks, a mid-prefill cancellation — streams
        byte-identical between ``decode_ticks=8`` and ``1``, with ONE
        decode program inclusive of the multi-tick geometry."""
        def drive(ticks):
            eng = _engine(model, decode_ticks=ticks,
                          prefix_cache=True, prefix_blocks=32)
            outs = []
            for wave in range(2):
                reqs = [_req(1, n=40, max_new_tokens=20),
                        _req(2, n=10, max_new_tokens=13),
                        _req(3, n=53, max_new_tokens=9,
                             temperature=0.9, top_k=5, seed=123),
                        _req(4, n=12, max_new_tokens=17,
                             temperature=0.8, top_k=4, seed=7)]
                seqs = [eng.submit(_clone(r)) for r in reqs]
                victim = eng.submit(_req(7, n=70))
                steps = 0
                while eng.has_work():
                    eng.step()
                    steps += 1
                    if steps == 4 and victim.status == "prefilling":
                        eng.cancel(victim)   # mid-chunk cancellation
                outs.append([s.tokens for s in seqs])
            return outs, eng

        want, base = drive(1)
        got, eng = drive(TICKS)
        assert got == want
        assert eng.decode_compilations() == 1
        assert eng.stats["mtick_syncs"] > 0
        assert eng.stats["mtick_ticks"] > eng.stats["mtick_syncs"]
        assert base.stats["mtick_syncs"] == 0
        # the fast path really amortized syncs: fewer decode launches
        assert eng.stats["decode_calls"] < base.stats["decode_calls"]

    def test_invalid_configs_raise(self, model):
        with pytest.raises(ValueError, match="decode_ticks"):
            _engine(model, decode_ticks=0)
        with pytest.raises(ValueError, match="spec_decode"):
            _engine(model, decode_ticks=4, spec_decode=True)


# --------------------------------------------------------- finish masking
class TestFinishMasking:
    """ISSUE 13 satellite: the on-device EOS/budget edges."""

    def _eos_case(self, model, ref, cut, max_new=24):
        """Run one request whose greedy stream hits EOS at output index
        ``cut``, at decode_ticks 1 and 8; returns both outcomes."""
        eos = ref[cut]
        assert eos not in ref[:cut], "ambiguous EOS plant"
        outs = []
        for ticks in (1, TICKS):
            eng = _engine(model, decode_ticks=ticks)
            seq = eng.submit(GenerationRequest(
                prompt=_prompt(5, 10), max_new_tokens=max_new,
                eos_token_id=eos))
            while eng.has_work():
                eng.step()
            # device append cut == host trim: every pool block handed
            # back at retirement (no trie on this engine)
            assert eng.cache.pool.num_free == eng.cache.pool.num_blocks
            outs.append((seq.tokens, seq.finish_reason, dict(eng.stats)))
        return outs

    def test_eos_on_tick0(self, model):
        ref = _greedy_ref(model)
        # output index 1 is the multi-tick step's tick 0 (output 0
        # comes from the prefill program)
        (t1, r1, _), (t8, r8, st) = self._eos_case(model, ref, 1)
        assert t1 == t8 and r1 == r8 == "stop"
        assert len(t8) == 2
        # the program retired the row at tick 0: one sync, one tick
        assert st["mtick_syncs"] == 1 and st["mtick_ticks"] == 1

    def test_eos_on_last_tick_of_block(self, model):
        ref = _greedy_ref(model)
        # output index 8 lands on tick n-1 of the first 8-tick block
        (t1, r1, _), (t8, r8, st) = self._eos_case(model, ref, 8)
        assert t1 == t8 and r1 == r8 == "stop"
        assert st["mtick_syncs"] == 1 and st["mtick_ticks"] == TICKS

    def test_eos_mid_block_returns_with_ticks_to_spare(self, model):
        ref = _greedy_ref(model)
        # first mid-block output index whose token is unambiguous
        cut = next(c for c in range(3, TICKS - 1)
                   if ref[c] not in ref[:c])
        (t1, r1, _), (t8, r8, st) = self._eos_case(model, ref, cut)
        assert t1 == t8 and r1 == r8 == "stop"
        # all slots finished early: the while_loop exited on the alive
        # mask, not the tick bound — ticks run < ticks requested
        assert st["last_decode_ticks"] < TICKS
        assert st["mtick_ticks"] == cut

    def test_budget_cut_mid_block(self, model):
        outs = []
        for ticks in (1, TICKS):
            eng = _engine(model, decode_ticks=ticks)
            seq = eng.submit(GenerationRequest(prompt=_prompt(5, 10),
                                               max_new_tokens=11))
            while eng.has_work():
                eng.step()
            assert eng.cache.pool.num_free == eng.cache.pool.num_blocks
            outs.append((seq.tokens, seq.finish_reason))
        (t1, r1), (t8, r8) = outs
        assert t1 == t8 and r1 == r8 == "length"
        assert len(t8) == 11

    def test_staggered_eos_rows_retire_independently(self, model):
        """Two slots whose EOS cuts land on different ticks of the
        same block: each trims at its own cut, the survivor keeps
        ticking on device."""
        ref = _greedy_ref(model)

        def drive(ticks):
            eng = _engine(model, decode_ticks=ticks)
            a = eng.submit(GenerationRequest(
                prompt=_prompt(5, 10), max_new_tokens=24,
                eos_token_id=ref[2]))
            b = eng.submit(GenerationRequest(
                prompt=_prompt(21, 14), max_new_tokens=15))
            while eng.has_work():
                eng.step()
            assert eng.cache.pool.num_free == eng.cache.pool.num_blocks
            return a.tokens, a.finish_reason, b.tokens, b.finish_reason

        assert drive(1) == drive(TICKS)

    def test_cancellation_mid_multitick_honored_at_sync_boundary(
            self, model):
        """cancel() runs on the driver thread, so it lands exactly at
        a sync boundary: the cancelled request keeps every token of
        completed blocks and nothing of the next, the bystander's
        stream is untouched, and the pool is exactly restored."""
        def drive(ticks, do_cancel):
            eng = _engine(model, decode_ticks=ticks)
            keep = eng.submit(_req(31, n=12, max_new_tokens=30))
            veto = eng.submit(_req(32, n=12, max_new_tokens=30))
            steps = 0
            while eng.has_work():
                eng.step()
                steps += 1
                if steps == 2 and do_cancel:
                    eng.cancel(veto)
            return keep.tokens, veto.tokens, veto.finish_reason, eng

        k8, v8, vr8, eng8 = drive(TICKS, True)
        k1, v1, _, _ = drive(1, False)
        assert vr8 == "cancelled"
        assert k8 == k1                      # bystander byte-identical
        # the cancelled stream is a prefix of its uncancelled self,
        # cut at a sync boundary (a whole number of accepted blocks)
        assert v8 == v1[:len(v8)]
        assert 0 < len(v8) < 30
        assert eng8.cache.pool.num_free == eng8.cache.pool.num_blocks


# ------------------------------------------------------ adaptive ticks
class _FakeSeq:
    def __init__(self, remaining):
        self.remaining = remaining


class TestAdaptiveTicks:
    def test_clamped_to_one_under_mixed_traffic(self):
        s = FIFOScheduler(1)
        s.enter_prefill("p")
        assert s.choose_decode_ticks([_FakeSeq(50)], 8) == 1

    def test_shrinks_to_nearest_guaranteed_retirement_when_queue_waits(
            self):
        s = FIFOScheduler(1)
        s.submit("waiting")
        active = [_FakeSeq(3), _FakeSeq(40)]
        # min remaining: the earliest guaranteed retirement lands on a
        # sync boundary, so the waiting request is never pushed past it
        assert s.choose_decode_ticks(active, 8) == 3

    def test_runs_to_largest_budget_when_idle(self):
        s = FIFOScheduler(1)
        active = [_FakeSeq(3), _FakeSeq(40)]
        # the alive mask retires the short row on device mid-block —
        # no shrinking the block for everyone
        assert s.choose_decode_ticks(active, 8) == 8
        assert s.choose_decode_ticks([_FakeSeq(5)], 8) == 5

    def test_degenerate_cases(self):
        s = FIFOScheduler(1)
        assert s.choose_decode_ticks([], 8) == 1
        assert s.choose_decode_ticks([_FakeSeq(50)], 1) == 1


# -------------------------------------------------------- fault interplay
def _mk_factory(model, **kw):
    kw.setdefault("prefix_cache", True)
    kw.setdefault("decode_ticks", TICKS)

    def factory():
        return _engine(model, **kw)
    return factory


def _traffic():
    return [_req(1, max_new_tokens=12), _req(2, n=10, max_new_tokens=12),
            _req(3, max_new_tokens=12, temperature=0.9, top_k=5,
                 seed=123),
            _req(4, n=60, max_new_tokens=6)]


#: one fault plan a kind, then all four in one run: each a case of its own
_CHAOS = {
    "transient": [(2, "transient")],
    "pool": [(4, "pool")],
    "fatal": [(6, "fatal")],
    "nan": [(4, "nan")],
    "all": [(2, "transient"), (4, "pool"), (6, "fatal"), (9, "nan")],
}


class TestFaultInterplay:
    @pytest.mark.parametrize("case", sorted(_CHAOS))
    def test_chaos_matrix_byte_identical(self, model, case):
        """The acceptance pin under faults: transient retry, pool
        exhaustion -> preemption, fatal rebuild and nan KV corruption
        all mid-multi-tick-traffic (each alone, then all in one run)
        — a fault unwinds to the last
        accepted token, restore() recomputes from accepted tokens
        only, streams land byte-identical to the fault-free
        ``decode_ticks=1`` oracle, and the rebuilt engine still counts
        ONE decode program."""
        reqs = _traffic()
        base = _engine(model, prefix_cache=True)
        want = [o.tolist()
                for o in base.generate([_clone(r) for r in reqs])]
        plan = FaultPlan()
        for step, kind in _CHAOS[case]:
            plan.at_step(step, kind)
        factory = _mk_factory(model)
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, start=False, max_queue=16,
                            retry_backoff_s=0.0)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == want
        fired = {k for _, k in plan.log}
        assert fired >= {kind for _, kind in _CHAOS[case]}
        if "fatal" in fired:
            assert gw.restarts >= 1
        assert gw.engine.decode_compilations() == 1
        assert gw.engine.decode_ticks == TICKS
        gw.shutdown(drain=True, timeout=30)


# ------------------------------------------------------- metrics surface
class TestMetricsSurface:
    def test_ticks_per_sync_gauge_and_dispatch_drop(self, model):
        """The satellite pin: ``serving_decode_ticks_per_sync`` > 1 on
        the multi-tick gateway, and the LIVE
        ``serving_dispatches_per_decoded_token`` gauge — the exact
        observatory counter, not a model — drops vs an identical
        ``decode_ticks=1`` gateway on the same decode-heavy traffic."""
        reqs = [_req(41, max_new_tokens=24),
                _req(42, n=10, max_new_tokens=24)]

        def run(ticks):
            factory = _mk_factory(model, prefix_cache=False,
                                  decode_ticks=ticks)
            gw = ServingGateway(factory(), engine_factory=factory,
                                start=False, max_queue=16)
            streams = [gw.submit(_clone(r)) for r in reqs]
            gw.start()
            outs = [st.result()[0].tolist() for st in streams]
            fams = parse_prometheus(gw.registry.render())

            def g(name):
                return fams[name]["samples"][(name, ())]
            ticks_per_sync = g("serving_decode_ticks_per_sync")
            dpt = g("serving_dispatches_per_decoded_token")
            mtick_disp = fams["serving_dispatches_total"]["samples"][
                ("serving_dispatches_total", (("program", "mtick"),))]
            gw.shutdown(drain=True, timeout=30)
            return outs, ticks_per_sync, dpt, mtick_disp

        outs1, tps1, dpt1, md1 = run(1)
        outs8, tps8, dpt8, md8 = run(TICKS)
        assert outs1 == outs8
        assert tps1 == 0.0 and md1 == 0    # baseline: gauge reads 0
        assert tps8 > 2.0                  # fast path engaged
        assert md8 > 0
        # the live exact counter shows the amortization directly
        assert dpt8 < dpt1 / 2.0

    def test_request_table_tpot_from_accepted_stamps(self, model):
        """ISSUE 13 satellite fix: /debug/requests derives TPOT-so-far
        from the last ACCEPTED token's stamp — two reads between the
        same two syncs must agree (the old clock-based numerator
        inflated for the whole step, freezing a stale-growing figure
        for n ticks under multi-tick decode)."""
        tick = itertools.count()
        clock = lambda: float(next(tick))   # noqa: E731
        factory = _mk_factory(model, prefix_cache=False,
                              step_clock=clock)
        gw = ServingGateway(factory(), engine_factory=factory,
                            start=False, max_queue=16)
        st = gw.submit(_req(51, max_new_tokens=30))
        # drive the gateway's own loop manually (single-threaded, so
        # reads land deterministically BETWEEN syncs)
        gw._admit_intake()
        for _ in range(3):
            gw._step_supervised()
        seq = st.seq
        assert len(seq.tokens) > 1
        row1 = [r for r in gw.request_table() if r["id"] == st.id][0]
        row2 = [r for r in gw.request_table() if r["id"] == st.id][0]
        # stamp-over-stamp: stable across repeated mid-flight reads,
        # even though each request_table() call reads the live clock
        assert row1["tpot_s"] is not None
        assert row1["tpot_s"] == row2["tpot_s"]
        want = (seq.t_last_token - seq.t_first_token) \
            / (len(seq.tokens) - 1)
        assert row1["tpot_s"] == pytest.approx(want, abs=1e-6)
        gw.shutdown(drain=False, timeout=10)
