"""The unified ragged serving step (README "Unified ragged attention"):
decode rows and prefill chunks ride ONE device program per step, with
the chunk grant adapted from measured headroom EWMAs. The load-bearing
properties (the streams themselves are held to ``model.forward`` in
``tests/test_serving_oracle.py``):

- **One launch**: a step carrying both a prefill chunk and live decode
  rows dispatches exactly ONE program — and no discarded decode row
  runs for a mid-prefill slot.
- **Headroom-adaptive budgeting**: the grant follows the measured
  tokens-per-second EWMA (deterministically, via an injected step
  clock), is capped at ``prefill_chunk``, and a throttled sub-block
  grant CARRIES to the next plan instead of starving the pipeline
  (the ``prefill_plan`` carry fix + its 1-token-over regression).
"""
import itertools

import pytest

from paddle_tpu.serving import FIFOScheduler, GenerationRequest

import serving_support
from serving_support import CHUNK, engine as _engine, prompt as _prompt


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


def _req(ps, n=40, **kw):
    kw.setdefault("max_new_tokens", 6)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


class TestOneLaunch:
    def test_mixed_step_single_program_no_dead_decode_row(self, model):
        """While a long prompt chunks, a step that ALSO decodes a live
        slot dispatches exactly one program, and the mid-prefill slot
        contributes its chunk span instead of a discarded full-length
        decode row."""
        calls = {"ragged": 0, "suffix": 0}
        eng = _engine(model, headroom_mult=None)
        orig_ragged, orig_sfx = eng._ragged_fn, eng._suffix_fn
        eng._ragged_fn = lambda n, rows: (calls.__setitem__(
            "ragged", calls["ragged"] + 1) or orig_ragged(n, rows))
        eng._suffix_fn = lambda: (calls.__setitem__(
            "suffix", calls["suffix"] + 1) or orig_sfx())
        short = eng.submit(_req(20, n=8, max_new_tokens=40))
        eng.step()                      # admit + first token
        longy = eng.submit(_req(21, n=80, max_new_tokens=4))
        while longy.status != "running":
            before = dict(calls)
            toks0 = len(short.tokens)
            eng.step()
            # one ragged launch; NO separate chunk program
            assert calls["ragged"] == before["ragged"] + 1
            assert calls["suffix"] == before["suffix"]
            assert len(short.tokens) == toks0 + 1   # decode kept going
        assert eng.stats["prefill_chunks"] == 5     # ceil(80/16)


class TestHeadroomBudget:
    def test_budget_defaults_to_cap_until_measured(self, model):
        eng = _engine(model)
        assert eng._prefill_budget() == CHUNK
        assert eng.stats["headroom"] == CHUNK

    def test_budget_tracks_measured_headroom_and_clamps(self, model):
        """The grant is tps_ewma x mult x decode-step-time minus the
        decode rows sharing the step, clamped to [1, cap]: fast packed
        steps pin it at the cap, slow ones throttle it toward 1."""
        eng = _engine(model, headroom_mult=2.0)
        eng._dt_decode_ewma = 0.010
        eng._tps_ewma = 2000.0          # 2k tok/s -> 40 affordable
        assert eng._prefill_budget() == CHUNK          # cap clamps
        eng._tps_ewma = 300.0           # 6 affordable
        assert eng._prefill_budget() == 6
        eng._tps_ewma = 10.0            # under a token -> floor at 1
        assert eng._prefill_budget() == 1
        assert eng.stats["headroom"] == 1
        with pytest.raises(ValueError, match="headroom_mult"):
            _engine(model, headroom_mult=0.0)

    def test_injected_clock_feeds_ewmas_deterministically(self, model):
        """``step_clock`` is the EWMAs' timebase: a virtual clock
        advancing 10 ms per reading yields exactly reproducible
        headroom stats — the hook the deterministic benches use. A step
        reads the clock at its start and at its fence; a program
        dispatched behind another costs the interval between the two
        fences, two readings. The decode baseline is fed only by the
        program that carries chunks: the unified step runs its
        chunk-free steps at the smaller packed size, so they feed
        nothing and the grant stays at the cap (on the multi-tick
        engine, whose one program carries both, they feed it)."""
        ticks = itertools.count()
        eng = _engine(model, step_clock=lambda: next(ticks) * 0.010)
        eng.generate([_req(30, n=50, max_new_tokens=3)])
        assert eng.stats["last_step_duration_s"] == pytest.approx(0.020)
        assert eng.stats["headroom_tps"] > 0      # chunk steps measured
        assert eng.stats["step_programs_8"] == 2  # the two decode steps
        assert eng._dt_decode_ewma is None
        assert eng._prefill_budget() == CHUNK == eng.stats["headroom"]
        ticks = itertools.count()
        eng = _engine(model, decode_ticks=2,
                      step_clock=lambda: next(ticks) * 0.010)
        eng.generate([_req(30, n=50, max_new_tokens=3)])
        assert eng._dt_decode_ewma == pytest.approx(0.010)

    def test_throttled_grant_still_completes_one_token_over(self, model):
        """The regression the plan-carry fix exists for: a prompt ONE
        token over the chunk cap, with the adaptive grant throttled to
        a single token per step, must still complete — sub-block
        grants accumulate at the plan head instead of serializing the
        queue behind the misaligned prompt."""
        eng = _engine(model)
        # pin the EWMAs so every grant is 1 token (floor)
        eng._tps_ewma = 1.0
        eng._dt_decode_ewma = 0.010
        bystander = eng.submit(_req(31, n=8, max_new_tokens=30))
        seq = eng.submit(_req(32, n=CHUNK + 1, max_new_tokens=3))
        steps = 0
        while not seq.done:
            eng.step()
            steps += 1
            assert steps < 300, "1-token-over prompt starved"
        assert seq.finish_reason == "length"
        want, _ = (lambda e: ([o.tolist() for o in e.generate(
            [_req(32, n=CHUNK + 1, max_new_tokens=3)])], e))(
            _engine(model, prefill_chunk=None))
        assert seq.tokens == want[0]
        while not bystander.done:
            eng.step()
        assert len(bystander.tokens) == 30


class TestSchedulerCarry:
    def test_sub_block_budgets_accumulate_at_plan_head(self):
        class S:
            def __init__(self, plen, done):
                self.work_len, self.prefilled = plen, done
        sched = FIFOScheduler()
        a = S(100, 0)
        sched.enter_prefill(a)
        # three sub-block grants accumulate, the fourth releases a block
        assert sched.prefill_plan(3, align=8) == []
        assert sched.prefill_plan(3, align=8) == []
        assert sched.prefill_plan(1, align=8) == []
        assert sched.prefill_plan(3, align=8) == [(a, 8)]
        # a granted plan consumes the carry — no double counting
        a.prefilled = 8
        assert sched.prefill_plan(16, align=8) == [(a, 16)]
        assert sched.prefill_plan(4, align=8) == []   # fresh carry: 4
        assert sched.prefill_plan(4, align=8) == [(a, 8)]

    def test_banked_carry_never_pushes_a_full_cap_grant_past_cap(self):
        """The overflow path the ``cap`` argument exists for: a
        throttled sub-block grant banks a carry, then the adaptive
        budget swings back to the full cap — the next plan must stay
        within ``cap`` tokens (the packed token buffer and the chunk
        compile bucket are sized for exactly that), not ``cap+carry``.
        A final chunk is the dangerous case: it skips block alignment,
        so an uncapped budget would hand out ``cap + carry`` tokens."""
        class S:
            def __init__(self, plen, done):
                self.work_len, self.prefilled = plen, done
        sched = FIFOScheduler()
        a = S(24 + 7, 0)                   # remaining > cap, final-chunk
        sched.enter_prefill(a)
        assert sched.prefill_plan(7, align=8, cap=24) == []
        assert sched._plan_carry == 7
        plan = sched.prefill_plan(24, align=8, cap=24)
        assert plan == [(a, 24)]           # clamped: NOT 24 + 7
        a.prefilled = 24
        # the tail completes on the next grant (carry was not needed)
        assert sched.prefill_plan(24, align=8, cap=24) == [(a, 7)]

    def test_carry_caps_at_one_block_and_clears_when_idle(self):
        class S:
            def __init__(self, plen, done):
                self.work_len, self.prefilled = plen, done
        sched = FIFOScheduler()
        a = S(40, 0)
        sched.enter_prefill(a)
        assert sched.prefill_plan(7, align=8) == []
        assert sched._plan_carry == 7
        sched.leave_prefill(a)
        # emptying the pipeline clears the carry EAGERLY — the engine
        # stops planning while idle, so a banked grant must not leak
        # into a later unrelated prompt's first plan
        assert sched._plan_carry == 0
        assert sched.prefill_plan(100, align=8) == []
        assert sched._plan_carry == 0


class TestMetricsSurface:
    def test_step_metrics_strict_parsed(self, model):
        """serving_step_duration_seconds (STEP_BUCKETS ladder),
        serving_step_tokens and serving_prefill_headroom_tokens land on
        /metrics, valid under the strict v0.0.4 parser, reading the
        same stats the adaptive budget does."""
        from test_metrics_prom import parse_prometheus

        from paddle_tpu.profiler.metrics import STEP_BUCKETS
        from paddle_tpu.serving.server import ServingGateway
        eng = _engine(model)
        gw = ServingGateway(eng, start=False)   # no driver thread needed
        # the gateway hooked ``engine.on_step``: every step program
        # fenced is observed, whoever pumps the engine
        eng.generate([_req(40, n=50, max_new_tokens=2)])
        fams = parse_prometheus(gw.registry.render())
        name = "serving_step_duration_seconds"
        assert fams[name]["type"] == "histogram"
        le = [k for k in fams[name]["samples"] if k[0] == name + "_bucket"]
        bounds = {lbl[1] for _, lbls in le for lbl in lbls
                  if lbl[0] == "le"}
        assert len(bounds) == len(STEP_BUCKETS) + 1  # ladder + +Inf
        assert fams[name]["samples"][(name + "_count", ())] == \
            eng.stats["unified_steps"] > 1
        assert fams["serving_step_tokens"]["type"] == "gauge"
        assert fams["serving_step_tokens"]["samples"][
            ("serving_step_tokens", ())] == eng.stats["last_step_tokens"]
        assert fams["serving_prefill_headroom_tokens"]["samples"][
            ("serving_prefill_headroom_tokens", ())] == \
            eng.stats["headroom"]
