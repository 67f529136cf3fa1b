"""Speculative multi-token decode on the paged path (ISSUE 9): draft →
ragged-span verify → block-tail rollback (README "Speculative
decoding"). The load-bearing properties:

- **Transparency**: token streams with speculation ON are
  byte-identical to speculation OFF — greedy AND seeded-sampled,
  across a hit/miss/chunked/cancel matrix — acceptance only reorders
  work; ``decode_compilations() == 1`` including the verify geometry.
- **Rollback accounting**: rejected draft K/V hands its blocks back
  exactly (``PagedKVCache.truncate``): num_free restored after full
  rejection, shared/donated prefix blocks never truncated, refcounts
  untouched, cancel-mid-verify restores the pool.
- **The speed structure**: with an accepting drafter a launch advances
  a slot by more than one token (fewer launches than tokens).
- **Drafters**: prompt-lookup n-gram proposals (model-free default)
  and the tiny-draft-model path behind one interface.
- **Fault interplay**: a fatal fault mid-speculation recovers
  byte-identically — ``restore()`` recomputes from ACCEPTED tokens
  only; unverified draft K/V never survives a rebuild.
"""
import numpy as np
import pytest

from paddle_tpu.serving import (BlockManager, Drafter, FaultPlan,
                                GenerationRequest, NgramDrafter,
                                PagedKVCache, FIFOScheduler)

import serving_support
from serving_support import (BS, clone as _clone, engine as _engine,
                             model_drafter as _model_drafter,
                             prompt as _prompt)

SPEC_K = 3


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA, pallas decode


def _req(ps, n=20, **kw):
    kw.setdefault("max_new_tokens", 8)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


class _Seq:
    """Host-only stand-in for drafter unit tests."""

    def __init__(self, prompt, tokens=()):
        self.prompt = np.asarray(prompt, np.int32)
        self.tokens = list(tokens)


class _JunkDrafter(Drafter):
    """Always proposes the same (almost surely wrong) tokens — the
    full-rejection instrument."""

    def propose(self, seq, k):
        return np.full(int(k), 7, np.int32)


class TestNgramDrafter:
    def test_matches_most_recent_ngram_continuation(self):
        d = NgramDrafter(max_ngram=3, min_ngram=1)
        #           0  1  2  3  4  5  6  7   tail [2,3] matches @2..3
        s = _Seq([9, 8, 2, 3, 5, 6, 2, 3])
        assert d.propose(s, 2).tolist() == [5, 6]
        # continuation capped at k
        assert d.propose(s, 1).tolist() == [5]

    def test_generated_tokens_extend_the_history(self):
        d = NgramDrafter()
        s = _Seq([1, 2, 3, 4], tokens=[1, 2])   # history ...3,4,1,2
        assert d.propose(s, 4).tolist() == [3, 4, 1, 2]

    def test_most_recent_occurrence_wins(self):
        d = NgramDrafter(max_ngram=1)
        s = _Seq([5, 1, 5, 2, 5])     # unigram 5: latest earlier @2
        assert d.propose(s, 1).tolist() == [2]

    def test_no_match_and_short_history_edges(self):
        d = NgramDrafter()
        assert d.propose(_Seq([1, 2, 3, 4]), 4).size == 0   # no repeat
        assert d.propose(_Seq([1]), 4).size == 0            # too short
        assert d.propose(_Seq([1, 1]), 0).size == 0         # k == 0
        # [1, 1]: unigram tail matches position 0, continuation = [1]
        assert d.propose(_Seq([1, 1]), 4).tolist() == [1]

    def test_validation(self):
        with pytest.raises(ValueError, match="max_ngram"):
            NgramDrafter(max_ngram=0)
        with pytest.raises(ValueError, match="max_ngram"):
            NgramDrafter(max_ngram=1, min_ngram=2)


class TestSpecGrants:
    def test_greedy_in_order_with_budget(self):
        s = FIFOScheduler()
        assert s.spec_grants([3, 3, 3], 5) == [3, 2, 0]
        assert s.spec_grants([2, 2], 10) == [2, 2]
        assert s.spec_grants([4], 0) == [0]
        assert s.spec_grants([4], -3) == [0]   # over-spent plan clamps
        assert s.spec_grants([], 7) == []


class TestTruncate:
    def _cache(self, blocks=12):
        pool = BlockManager(1, blocks, BS, 1, 4)
        return PagedKVCache(1, 2, 6 * BS, 1, 4, block_size=BS,
                            pool=pool), pool

    def test_frees_exactly_the_private_tail(self):
        cache, pool = self._cache()
        slot = cache.alloc()
        cache.ensure_capacity(slot, 4 * BS)       # 4 private blocks
        assert pool.num_free == 12 - 4
        cache.lengths[slot] = 2 * BS + 3
        cache.truncate(slot, BS + 2)              # keep ceil(10/8) = 2
        assert pool.num_free == 12 - 2
        assert int(cache._n_blocks[slot]) == 2
        assert int(cache.lengths[slot]) == BS + 2     # clamped down
        assert all(int(b) == cache.sentinel
                   for b in cache.tables[slot, 2:])
        # covering rows: no-op
        cache.truncate(slot, BS + 2)
        assert pool.num_free == 12 - 2
        # regrowth reuses the heap
        cache.ensure_capacity(slot, 4 * BS)
        assert pool.num_free == 12 - 4

    def test_never_touches_shared_prefix_blocks(self):
        cache, pool = self._cache()
        shared = [pool.alloc(), pool.alloc()]
        for b in shared:
            pool.ref(b)                  # the trie's pins (readers')
        slot = cache.alloc()
        cache.install_prefix(slot, shared)
        cache.ensure_capacity(slot, 3 * BS)   # + 1 private block
        refs_before = [pool.refcount(b) for b in shared]
        free_before = pool.num_free
        # rows=0 would reach into the shared prefix: clamped, only the
        # private tail drops
        cache.truncate(slot, 0)
        assert [pool.refcount(b) for b in shared] == refs_before
        assert int(cache._n_blocks[slot]) == 2
        assert pool.num_free == free_before + 1
        for j, b in enumerate(shared):
            assert int(cache.tables[slot, j]) == b   # still installed


class TestValidation:
    def test_spec_k_validated(self, model):
        with pytest.raises(ValueError, match="spec_k"):
            _engine(model, spec_decode=True, spec_k=0)


class TestTransparency:
    @pytest.mark.slow  # 17 s transparency matrix duplicate: the drafter/eos/
    # compile-isolation reps below run by default (870s cap)
    def test_spec_equals_baseline_mixed_matrix(self, model):
        """The acceptance pin: a hit/miss/chunked/cancel traffic matrix
        — shared system prompt, greedy and seeded-sampled rows, a long
        prompt that chunks, a mid-prefill cancellation — streams
        byte-identical between ``spec_decode=True`` (prompt-lookup
        drafts) and speculation off, with ONE verify-program trace."""
        sysp = _prompt(90, 32)

        def drive(spec):
            eng = _engine(model, spec_decode=spec, spec_k=SPEC_K,
                          prefix_cache=True, prefix_blocks=32)
            outs = []
            for wave in range(2):
                reqs = [_req(1, n=40), _req(2, n=21),
                        GenerationRequest(
                            prompt=np.concatenate([sysp, _prompt(3, 9)]),
                            max_new_tokens=6),
                        GenerationRequest(
                            prompt=np.concatenate([sysp, _prompt(4, 15)]),
                            max_new_tokens=5, temperature=0.8, top_k=4,
                            seed=7),
                        _req(5, n=33, temperature=0.9, top_k=5, seed=123)]
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

        want, base = drive(False)
        got, eng = drive(True)
        assert got == want
        assert eng.decode_compilations() == 1
        assert eng.stats["spec_steps"] > 0
        assert eng.stats["spec_proposed"] > 0
        assert base.stats["spec_steps"] == 0
        assert eng.prefix_cache.stats["hits"] >= 1
        assert eng.stats["prefill_chunks"] >= 1   # chunks rode the
        # same one-launch-per-step verify program

    def test_decode_compilations_isolates_spec_k_variants(self, model):
        """Two spec engines sharing one jit cache and a packed budget
        (the chunk term of the max dominates both) but differing in
        spec_k trace two DIFFERENT verify programs — each engine must
        count exactly its own (the spec_len key-filter regression)."""
        a = _engine(model, spec_decode=True, spec_k=2)
        b = _engine(model, spec_decode=True, spec_k=3)
        assert a._spec_budget == b._spec_budget   # the hazard is real
        a.generate([_req(91, max_new_tokens=3)])
        b.generate([_req(92, max_new_tokens=3)])
        assert a.decode_compilations() == 1
        assert b.decode_compilations() == 1

    @pytest.mark.slow  # 6 s launch-count duplicate: the eos and compile-
    # isolation reps in this class run by default (870s cap)
    def test_accepting_drafter_fewer_launches_than_tokens(self, model):
        """With the always-accept oracle (the target model drafting for
        itself) a launch advances a slot by up to spec_k + 1 tokens:
        fewer verify launches than generated tokens, streams still
        byte-identical — the speed structure the bench banks."""
        want = [o.tolist() for o in _engine(model).generate(
            [_req(11, max_new_tokens=12), _req(12, max_new_tokens=12)])]
        eng = _engine(model, spec_decode=True, spec_k=SPEC_K,
                      drafter=_model_drafter(model))
        launches = {"n": 0}
        orig = eng._spec_fn
        eng._spec_fn = lambda: (launches.__setitem__(
            "n", launches["n"] + 1) or orig())
        outs = eng.generate(
            [_req(11, max_new_tokens=12), _req(12, max_new_tokens=12)])
        assert [o.tolist() for o in outs] == want
        assert eng.stats["spec_accepted"] > 0
        assert launches["n"] < eng.stats["spec_tokens"]
        # greedy self-drafting accepts fully: mean emitted per span > 2
        assert eng.stats["spec_tokens"] > 2 * launches["n"]

    def test_eos_mid_acceptance_stops_the_stream(self, model):
        """An accepted draft token equal to EOS must finish the
        sequence exactly where sequential decode would — tokens past it
        are never emitted even when the verify accepted further."""
        base = _engine(model).generate(
            [_req(21, max_new_tokens=24, eos_token_id=3)])
        eng = _engine(model, spec_decode=True, spec_k=SPEC_K,
                      drafter=_model_drafter(model))
        outs = eng.generate([_req(21, max_new_tokens=24, eos_token_id=3)])
        assert [o.tolist() for o in outs] == [b.tolist() for b in base]
        assert outs[0].finish_reason == base[0].finish_reason


class TestRollbackAccounting:
    def test_full_rejection_restores_pool_exactly(self, model):
        """A drafter that is always wrong: every verify writes k draft
        rows and truncates them all back. Streams stay byte-identical
        (the correction token is the model's own) and after retirement
        the pool is exactly restored — no leaked, no double-freed
        blocks."""
        want = [o.tolist() for o in _engine(model).generate(
            [_req(31), _req(32, n=33)])]
        eng = _engine(model, spec_decode=True, spec_k=SPEC_K,
                      drafter=_JunkDrafter())
        pool = eng.cache.pool
        nfree0 = pool.num_free
        outs = eng.generate([_req(31), _req(32, n=33)])
        assert [o.tolist() for o in outs] == want
        assert eng.stats["spec_proposed"] > 0
        # junk drafts verified and rolled back; occasional flukes aside
        # the acceptance stays near zero
        assert eng.stats["spec_accepted"] <= eng.stats["spec_proposed"] / 2
        assert pool.num_free == nfree0
        assert int((pool._ref > 0).sum()) == 0

    def test_cancel_mid_verify_restores_pool(self, model):
        eng = _engine(model, spec_decode=True, spec_k=SPEC_K,
                      drafter=_model_drafter(model))
        pool = eng.cache.pool
        nfree0 = pool.num_free
        seq = eng.submit(_req(41, max_new_tokens=40))
        other = eng.submit(_req(42, max_new_tokens=6))
        for _ in range(3):
            eng.step()
        assert seq.status == "running"
        eng.cancel(seq)                  # mid-speculation teardown
        while eng.has_work():
            eng.step()
        assert other.done and seq.finish_reason == "cancelled"
        assert pool.num_free == nfree0
        assert int((pool._ref > 0).sum()) == 0

    def test_donated_blocks_survive_rollback_traffic(self, model):
        """With the prefix trie on, retirement donates written chains;
        later speculative traffic truncates only private tails — every
        pool block ends up free or trie-owned, refcounts exact."""
        eng = _engine(model, spec_decode=True, spec_k=SPEC_K,
                      prefix_cache=True, prefix_blocks=16,
                      drafter=_JunkDrafter())
        reqs = [_req(51, n=24, max_new_tokens=10),
                _req(51, n=24, max_new_tokens=10),   # hits the donation
                _req(52, n=17, max_new_tokens=10)]
        for r in reqs:
            eng.generate([r])
        pool = eng.cache.pool
        trie_blocks = eng.prefix_cache.num_cached_blocks
        assert pool.num_used == trie_blocks      # free or trie-owned
        assert int((pool._ref > 0).sum()) == 0   # trie holds no pins
        assert eng.prefix_cache.stats["hits"] >= 1


class TestFaultInterplay:
    def test_fatal_mid_speculation_recovers_byte_identical(self, model):
        """The chaos satellite: a NaN-corrupting fatal fault lands
        while drafts are in flight; the supervisor rebuilds and
        ``restore()`` recomputes from ACCEPTED tokens only, so every
        stream continues byte-identically — unverified draft K/V (and
        the corrupted pool) never survive the rebuild."""
        from paddle_tpu.serving.server import ServingGateway
        reqs = [_req(61, max_new_tokens=10), _req(62, n=26,
                                                  max_new_tokens=10),
                _req(63, temperature=0.9, top_k=5, seed=9,
                     max_new_tokens=8)]
        want = [o.tolist() for o in _engine(model).generate(
            [_clone(r) for r in reqs])]
        drafter = _model_drafter(model)

        def factory():
            return _engine(model, spec_decode=True, spec_k=SPEC_K,
                           drafter=drafter)

        plan = FaultPlan().at_step(4, "nan")
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, max_restarts=4,
                            retry_backoff_s=0.0, start=False)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        gw.shutdown(drain=True, timeout=60)
        assert [list(ids) for ids, _ in outs] == want
        assert gw.restarts == 1
        assert plan.log == [(4, "nan")]
        assert gw.engine.decode_compilations() == 1   # shared factory
        # cache: the rebuild re-traced nothing

    @pytest.mark.slow  # 6 s fault duplicate: test_fatal_mid_speculation_
    # recovers_byte_identical above is the default fault rep (870s cap)
    def test_restore_recomputes_from_accepted_tokens_only(self, model):
        """Engine-level restore pin: displace a speculating sequence
        mid-flight; its recompute work is prompt + ACCEPTED tokens
        (drafts never entered ``seq.tokens``) and the continuation is
        byte-identical."""
        want = _engine(model).generate(
            [_req(71, max_new_tokens=14)])[0].tolist()
        eng = _engine(model, spec_decode=True, spec_k=SPEC_K,
                      drafter=_model_drafter(model))
        seq = eng.submit(_req(71, max_new_tokens=14))
        for _ in range(3):
            eng.step()
        assert 0 < len(seq.tokens) < 14
        eng._preempt(seq)                 # donate + requeue (recompute)
        assert seq.status == "queued"
        assert len(seq.work) == seq.prompt_len + len(seq.tokens) - 1
        while eng.has_work():
            eng.step()
        assert seq.tokens == want


class TestMetricsSurface:
    def test_spec_metrics_strict_parsed(self, model):
        """serving_spec_proposed_total / serving_spec_accepted_total,
        the serving_spec_accept_length histogram (SPEC_ACCEPT_BUCKETS
        ladder) and the launches-per-accepted-token gauge land on
        /metrics, valid under the strict v0.0.4 parser, reading the
        engine's own stats."""
        from test_metrics_prom import parse_prometheus

        from paddle_tpu.profiler.metrics import SPEC_ACCEPT_BUCKETS
        from paddle_tpu.serving.server import ServingGateway
        drafter = _model_drafter(model)

        def factory():
            return _engine(model, spec_decode=True, spec_k=SPEC_K,
                           drafter=drafter)

        gw = ServingGateway(factory(), engine_factory=factory,
                            start=False)
        streams = [gw.submit(_req(81, max_new_tokens=10)),
                   gw.submit(_req(82, max_new_tokens=8))]
        gw.start()
        for st in streams:
            st.result()
        eng = gw.engine
        # scrape after the driver exits: the acceptance-length drain
        # runs post-step on the driver thread
        gw.shutdown(drain=True, timeout=60)
        fams = parse_prometheus(gw.registry.render())
        assert fams["serving_spec_proposed_total"]["samples"][
            ("serving_spec_proposed_total", ())] == \
            eng.stats["spec_proposed"]
        assert fams["serving_spec_accepted_total"]["samples"][
            ("serving_spec_accepted_total", ())] == \
            eng.stats["spec_accepted"]
        name = "serving_spec_accept_length"
        assert fams[name]["type"] == "histogram"
        le = [k for k in fams[name]["samples"] if k[0] == name + "_bucket"]
        bounds = {lbl[1] for _, lbls in le for lbl in lbls
                  if lbl[0] == "le"}
        assert len(bounds) == len(SPEC_ACCEPT_BUCKETS) + 1   # + +Inf
        # the driver drained every verify span into the histogram: the
        # observation total is the emitted-token total, one acceptance
        # length per span
        assert fams[name]["samples"][(name + "_sum", ())] == \
            eng.stats["spec_tokens"]
        assert fams[name]["samples"][(name + "_count", ())] > 0
        assert eng.stats["spec_last_accept"] == []   # fully drained
        # decode_calls, not spec_steps: chunk-only launches carry no
        # verify rows and must not inflate the launches-per-token ratio
        g = "serving_spec_launches_per_accepted_token"
        assert fams[g]["samples"][(g, ())] == pytest.approx(
            eng.stats["decode_calls"] / max(eng.stats["spec_tokens"], 1))
