"""Continuous-batching serving engine (serving/engine.py, SURVEY §3.5 /
PAPERS.md): the KV cache's slots, mid-flight admission, EOS early-exit, per-slot
sampling params, and the compile-once contract of the decode step
function. The load-bearing property throughout: a request's token stream
depends only on its own prompt/key — never on batch composition or
admission timing."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import (GenerationRequest, FIFOScheduler,
                                PagedKVCache)

import serving_support


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=21)  # GQA: nkv=2 < nh=4


def _engine(model, **kw):
    """The shared helper at 48 positions and otherwise the ENGINE's own
    defaults (decode fused 8 steps a call, no chunking): what
    ``model.generate`` builds, which these tests hold the engine to."""
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 48)
    return serving_support.engine_as_given(model, **kw)


def _prompt(seed, n=8):
    return serving_support.prompt(seed, n)


def _solo(model, req, **ekw):
    out = _engine(model, **ekw).generate([req])[0]
    return out.tolist()


class TestEngineBasics:
    @pytest.mark.slow  # 9 s generate-parity duplicate: test_mid_flight_admission_
    # matches_solo and the pallas/jnp identity test keep the default reps (870s cap)
    def test_greedy_matches_model_generate(self, model):
        ids = np.stack([_prompt(0), _prompt(1)])
        want = model.generate(paddle.to_tensor(ids), max_new_tokens=6).numpy()
        outs = _engine(model).generate(
            [GenerationRequest(prompt=ids[i], max_new_tokens=6)
             for i in range(2)])
        np.testing.assert_array_equal(np.stack(outs), want)

    @pytest.mark.slow  # slot-recycling duplicate (bigger traffic of
    # the same property): test_slot_reuse_after_finish and the
    # scheduler unit tests stay the default reps
    def test_queue_longer_than_slots(self, model):
        """5 requests through 2 slots: all finish, all correct."""
        reqs = [GenerationRequest(prompt=_prompt(i), max_new_tokens=4)
                for i in range(5)]
        eng = _engine(model)
        outs = eng.generate(reqs)
        assert len(outs) == 5 and all(len(o) == 4 for o in outs)
        solo = [_solo(model, r) for r in reqs]
        for o, s in zip(outs, solo):
            assert o.tolist() == s
        assert eng.cache.num_free == eng.num_slots  # all slots returned

    def test_submit_validation(self, model):
        eng = _engine(model)
        with pytest.raises(ValueError, match="KV cache"):
            eng.submit(GenerationRequest(prompt=_prompt(0, 40),
                                         max_new_tokens=9))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(GenerationRequest(prompt=_prompt(0), max_new_tokens=0))


class TestDecodePathEquivalence:
    @pytest.mark.slow  # engine-level pallas≡jnp (two engine builds);
    # the kernel-vs-oracle suites (test_pallas_decode,
    # test_pallas_paged_decode) and test_decode's program-level parity
    # stay the default reps of the same chain
    def test_pallas_and_jnp_tokens_identical(self):
        """The ragged Pallas decode kernel and the jnp oracle produce the
        same greedy continuation AND the same sampled continuation under
        one seed (token-exact, GQA included)."""
        outs = {}
        for attn in ("pallas", "jnp"):
            eng = _engine(serving_support.model("llama", seed=33,
                                                decode_attention=attn))
            outs[attn] = eng.generate([
                GenerationRequest(prompt=_prompt(3), max_new_tokens=8),
                GenerationRequest(prompt=_prompt(4), max_new_tokens=8,
                                  temperature=0.8, top_k=7, seed=11)])
        np.testing.assert_array_equal(outs["pallas"][0], outs["jnp"][0])
        np.testing.assert_array_equal(outs["pallas"][1], outs["jnp"][1])


class TestEOS:
    @pytest.mark.slow  # 6 s EOS duplicate: test_generate_eos_pads_output below
    # is the default EOS rep (870s cap)
    def test_eos_early_exit_frees_slot(self, model):
        req = GenerationRequest(prompt=_prompt(5), max_new_tokens=12)
        free_run = _solo(model, req)
        eos = free_run[2]
        stop_at = free_run.index(eos)  # first occurrence wins
        eng = _engine(model)
        seq = eng.submit(GenerationRequest(
            prompt=_prompt(5), max_new_tokens=12, eos_token_id=eos))
        while eng.has_work():
            eng.step()
        assert seq.finish_reason == "stop"  # OpenAI-style reason for EOS
        assert seq.tokens == free_run[:stop_at + 1]  # EOS included
        assert eng.cache.num_free == eng.num_slots
        assert eng.cache.lengths[seq.slot] == 0  # slot really reset

    def test_generate_eos_pads_output(self, model):
        req = GenerationRequest(prompt=_prompt(5), max_new_tokens=12)
        eos = _solo(model, req)[2]
        out = model.generate(paddle.to_tensor(_prompt(5)[None]),
                             max_new_tokens=12, eos_token_id=eos).numpy()
        assert out.shape == (1, 12)
        first = out[0].tolist().index(eos)
        assert all(t == eos for t in out[0][first:])


class TestContinuousBatching:
    def test_mid_flight_admission_matches_solo(self, model):
        """A request admitted into a slot freed mid-flight produces the
        exact tokens of its solo run — greedy and sampled both."""
        late_g = GenerationRequest(prompt=_prompt(6), max_new_tokens=6)
        late_s = GenerationRequest(prompt=_prompt(7), max_new_tokens=6,
                                   temperature=0.9, top_k=5, seed=123)
        solo_g = _solo(model, late_g)
        solo_s = _solo(model, late_s)

        eng = _engine(model, decode_chunk=1)
        long_seq = eng.submit(GenerationRequest(prompt=_prompt(8),
                                                max_new_tokens=20))
        short = eng.submit(GenerationRequest(prompt=_prompt(9),
                                             max_new_tokens=3))
        for _ in range(5):  # short finishes, long still mid-flight
            eng.step()
        assert short.done and not long_seq.done
        lg = eng.submit(late_g)  # admitted into short's freed slot
        for _ in range(3):
            eng.step()
        ls = eng.submit(late_s)  # second reuse, while decode continues
        while eng.has_work():
            eng.step()
        assert lg.tokens == solo_g and ls.tokens == solo_s
        assert long_seq.done and len(long_seq.tokens) == 20

    def test_slot_reuse_after_finish(self, model):
        eng = _engine(model, num_slots=1)
        a = eng.submit(GenerationRequest(prompt=_prompt(10), max_new_tokens=3))
        b = eng.submit(GenerationRequest(prompt=_prompt(11), max_new_tokens=3))
        while eng.has_work():
            eng.step()
        assert a.slot == b.slot == 0  # same physical slot, serially reused
        assert b.tokens == _solo(model, b.request)
        assert eng.stats["prefills"] == 2

    def test_fused_chunks_match_single_steps(self, model):
        """decode_chunk>1 (multi-step fused scan) changes dispatch count,
        never tokens."""
        reqs = [GenerationRequest(prompt=_prompt(12), max_new_tokens=17),
                GenerationRequest(prompt=_prompt(13), max_new_tokens=17,
                                  temperature=0.7, top_k=9, seed=3)]
        eng1 = _engine(model, decode_chunk=1)
        outs1 = eng1.generate([GenerationRequest(**{
            k: getattr(r, k) for k in ("prompt", "max_new_tokens",
                                       "temperature", "top_k", "seed")})
            for r in reqs])
        eng8 = _engine(model, decode_chunk=8)
        outs8 = eng8.generate(reqs)
        for a, b in zip(outs1, outs8):
            np.testing.assert_array_equal(a, b)
        assert eng8.stats["decode_calls"] < eng1.stats["decode_calls"]


class TestCompileOnce:
    def test_decode_compiles_once_across_request_mixes(self, model):
        """One decode trace serves every (max_new, temperature, top_k)
        mix — the knob arrays are runtime values, not trace constants."""
        # programs of its own: the count is over every n_steps of this
        # geometry, and the module's other engines fuse 8 steps a call. On
        # the jnp attention path, the cheapest step program there is to lower
        model = serving_support.model("llama", seed=21,
                                      decode_attention="jnp")
        eng = _engine(model, decode_chunk=1, jit_cache={})
        eng.generate([GenerationRequest(prompt=_prompt(14), max_new_tokens=4)])
        assert eng.decode_compilations() == 1
        eng.generate([
            GenerationRequest(prompt=_prompt(15), max_new_tokens=7,
                              temperature=1.3, top_k=11, seed=8),
            GenerationRequest(prompt=_prompt(16, n=5), max_new_tokens=2,
                              temperature=0.4, top_k=0, seed=9)])
        assert eng.decode_compilations() == 1

    @pytest.mark.slow  # model.generate compile-reuse duplicate:
    # test_generate's jit-cache-reused + engine≡model.generate
    # (test_greedy_matches_model_generate) and the engine-level
    # request-mix closure stay the default reps
    def test_model_generate_shares_decode_program(self, model):
        """model.generate() rides the same compile-once contract when the
        cache length is pinned: sampling-knob changes add no traces.
        (model.generate inherits the paged engine default, so the
        programs counted are the unified "ragged" kind.)"""
        t = paddle.to_tensor(np.stack([_prompt(17)]))
        m = model

        def decode_traces():
            return sum(fn._cache_size()
                       for key, fn in m._serving_jit.items()
                       if key[0] == "ragged")

        before = decode_traces()  # other tests share this model's cache
        m.generate(t, max_new_tokens=6, max_cache_len=32)
        n0 = decode_traces()
        # sampling-knob changes: zero new decode traces
        m.generate(t, max_new_tokens=6, temperature=0.7, top_k=3,
                   seed=1, max_cache_len=32)
        m.generate(t, max_new_tokens=6, temperature=1.1, top_k=0,
                   seed=2, max_cache_len=32)
        assert decode_traces() == n0
        # a different token budget may add pow2 step sizes but stays
        # within the bounded level set {1, 2, 4, ..., decode_chunk}
        m.generate(t, max_new_tokens=4, max_cache_len=32)
        import math
        chunk = 16  # model.generate's engine decode_chunk
        assert decode_traces() - before <= int(math.log2(chunk)) + 1


class TestFinishReasons:
    """Engine-level finish_reason surface (no gateway involved): the
    closed vocabulary stop|length|cancelled|timeout, surfaced both on
    the Sequence handle and on generate()'s GenerationResult."""

    def test_generate_results_carry_finish_reason(self, model):
        from paddle_tpu.serving import GenerationResult
        eng = _engine(model)
        probe = eng.generate([GenerationRequest(prompt=_prompt(30),
                                                max_new_tokens=8)])[0]
        eos = probe[2]
        outs = eng.generate([
            GenerationRequest(prompt=_prompt(30), max_new_tokens=8,
                              eos_token_id=int(eos)),
            GenerationRequest(prompt=_prompt(31), max_new_tokens=4)])
        assert all(isinstance(o, GenerationResult) for o in outs)
        assert outs[0].finish_reason == "stop"
        assert outs[1].finish_reason == "length"
        # array-likeness: the old ndarray call sites keep working
        assert len(outs[1]) == 4
        np.testing.assert_array_equal(np.stack([outs[1], outs[1]])[0],
                                      outs[1].ids)

    def test_cancel_running_frees_slot_mid_decode(self, model):
        eng = _engine(model, decode_chunk=1)
        victim = eng.submit(GenerationRequest(prompt=_prompt(32),
                                              max_new_tokens=30))
        bystander = eng.submit(GenerationRequest(prompt=_prompt(33),
                                                 max_new_tokens=10))
        solo = _solo(model, bystander.request)
        for _ in range(4):
            eng.step()
        assert victim.status == "running"
        free_before = eng.cache.num_free
        assert eng.cancel(victim) is True
        assert victim.finish_reason == "cancelled"
        assert eng.cache.num_free == free_before + 1  # slot back NOW
        assert eng.cache.lengths[victim.slot] == 0
        assert eng.cancel(victim) is False  # idempotent on finished
        while eng.has_work():
            eng.step()
        assert bystander.tokens == solo  # cancel never perturbs others
        assert eng.stats["cancelled"] == 1

    def test_cancel_queued_never_prefills(self, model):
        eng = _engine(model, num_slots=1)
        hog = eng.submit(GenerationRequest(prompt=_prompt(34),
                                           max_new_tokens=6))
        queued = eng.submit(GenerationRequest(prompt=_prompt(35),
                                              max_new_tokens=6))
        eng.step()  # hog takes the only slot
        assert queued.status == "queued"
        assert eng.cancel(queued) is True
        while eng.has_work():
            eng.step()
        assert queued.finish_reason == "cancelled"
        assert eng.stats["prefills"] == 1  # only the hog ever prefilled
        assert hog.finish_reason == "length"

    def test_timeout_running_and_queued(self, model):
        import time as _time
        eng = _engine(model, num_slots=1, max_seq_len=64, decode_chunk=1)
        # warm the programs so the deadline measures steps, not compiles
        eng.generate([GenerationRequest(prompt=_prompt(36),
                                        max_new_tokens=2)])
        runner = eng.submit(GenerationRequest(
            prompt=_prompt(36), max_new_tokens=50, timeout_s=0.03))
        starved = eng.submit(GenerationRequest(
            prompt=_prompt(37), max_new_tokens=4, timeout_s=0.01))
        prefills0 = eng.stats["prefills"]
        while eng.has_work():
            eng.step()
            _time.sleep(0.002)  # keep wall moving on fast boxes
        assert runner.finish_reason == "timeout"
        assert 0 < len(runner.tokens) < 50  # partial output preserved
        # the starved request expired in the queue: no slot, no prefill
        # (the +1 is the runner's own admission)
        assert starved.finish_reason == "timeout"
        assert starved.tokens == []
        assert eng.stats["prefills"] == prefills0 + 1
        assert eng.stats["timeouts"] == 2
        assert eng.cache.num_free == eng.num_slots

    def test_timeout_validation(self, model):
        eng = _engine(model)
        with pytest.raises(ValueError, match="timeout_s"):
            eng.submit(GenerationRequest(prompt=_prompt(38),
                                         max_new_tokens=2, timeout_s=0))

    def test_on_token_callback_streams_every_token(self, model):
        """on_token fires once per generated token in order, including
        the prefill-sampled first token — the gateway's wire."""
        eng = _engine(model, decode_chunk=1)
        seen = []
        eng.on_token = lambda seq, tok: seen.append((seq.request_id, tok))
        done = []
        eng.on_finish = lambda seq: done.append(seq.request_id)
        seq = eng.submit(GenerationRequest(prompt=_prompt(39),
                                           max_new_tokens=5))
        while eng.has_work():
            eng.step()
        assert [t for _, t in seen] == seq.tokens
        assert done == [seq.request_id]


class TestKVCacheManager:
    def test_alloc_free_cycle(self):
        c = PagedKVCache(2, 3, 16, 2, 8)
        slots = [c.alloc() for _ in range(3)]
        assert slots == [0, 1, 2] and c.alloc() is None
        c.free(1)
        assert c.num_free == 1 and c.alloc() == 1
        with pytest.raises(ValueError, match="double-freed"):
            c.free(1) or c.free(1)

    def test_lengths_reset_on_free(self):
        c = PagedKVCache(2, 2, 16, 2, 8)
        s = c.alloc()
        c.lengths[s] = 9
        c.free(s)
        assert c.lengths[s] == 0

    def test_heap_allocator_deterministic_and_double_free_guarded(self):
        """The heap+set allocator (replacing the O(n) list scan /
        sort-on-alloc): lowest-free-index order survives interleaved
        frees, and the double-free guard stays O(1) AND correct across
        alloc/free cycles — the regression the membership set pins."""
        c = PagedKVCache(2, 4, 16, 2, 8)
        assert [c.alloc() for _ in range(4)] == [0, 1, 2, 3]
        c.free(2)
        c.free(0)
        c.free(3)
        assert c.alloc() == 0          # lowest index first, always
        assert c.alloc() == 2
        c.free(2)                      # re-free after re-alloc is legal
        with pytest.raises(ValueError, match="double-freed"):
            c.free(2)                  # immediate double-free caught
        assert c.alloc() == 2          # guard never corrupted the pool
        assert c.alloc() == 3 and c.alloc() is None
        assert c.num_free == 0


class TestScheduler:
    def test_fifo_admission_order(self):
        sched = FIFOScheduler()
        sched.submit("a"); sched.submit("b"); sched.submit("c")
        assert sched.admissions(2) == ["a", "b"]
        assert sched.admissions(2) == ["c"]

    def test_remove_while_queued_vs_after_admission_pop(self):
        """remove() edge cases: a queued sequence is droppable exactly
        once; a sequence already popped by admissions() (mid-admission
        group, no longer the scheduler's to drop) returns False — the
        engine relies on that to distinguish 'never claims a slot' from
        'already being prefilled' in cancel/deadline paths."""
        sched = FIFOScheduler()
        sched.submit("a"); sched.submit("b"); sched.submit("c")
        assert sched.remove("b") is True       # queued: dropped
        assert sched.remove("b") is False      # idempotent
        popped = sched.admissions(2)
        assert popped == ["a", "c"]
        assert sched.remove("a") is False      # mid-admission: not ours
        assert sched.num_queued == 0
        sched.submit("d")
        assert sched.remove("d") is True and sched.num_queued == 0

    def test_hit_aware_admission_orders_by_suffix_keeps_fifo_set(self):
        """With a hit_len_fn the admitted SET is still the FIFO head
        (fairness), ordered by ascending uncovered suffix so same-bucket
        prefills group; ties keep FIFO order (stable sort)."""
        class S:
            def __init__(self, name, plen):
                # work_len is what admission orders by (== prompt_len
                # unless restored for recovery-by-recompute)
                self.name, self.work_len = name, plen
                self.prefix_hit_tokens = 0
        a, b, c, d = S("a", 40), S("b", 48), S("c", 40), S("d", 8)
        sched = FIFOScheduler()
        for s in (a, b, c, d):
            sched.submit(s)
        hits = {"a": 0, "b": 32, "c": 0}
        out = sched.admissions(3, hit_len_fn=lambda s: hits[s.name])
        # d never jumps the line despite its tiny prompt
        assert [s.name for s in out] == ["b", "a", "c"]  # suffixes 16,40,40
        assert out[0].prefix_hit_tokens == 32
        assert [s.name for s in sched.admissions(2)] == ["d"]

    def test_chunk_fusion_policy(self):
        class S:  # stub sequence
            def __init__(self, remaining):
                self.remaining = remaining

        sched = FIFOScheduler(decode_chunk=8)
        assert sched.choose_num_steps([S(20), S(9)]) == 8
        # near-finisher: largest pow2 within its remaining budget
        assert sched.choose_num_steps([S(20), S(7)]) == 4
        assert sched.choose_num_steps([S(20), S(1)]) == 1
        sched.submit("queued")
        assert sched.choose_num_steps([S(20), S(20)]) == 1  # admission due
