"""Block-table paged attention (serving/kv_cache.PagedKVCache): zero-copy
prefix hits over a shared block pool.

The load-bearing properties:

- **Transparency**: token streams with the prefix cache on are
  byte-identical to the cache-off engine's — greedy AND seeded sampled —
  across hits, misses, evictions, COW divergence, and fused decode
  chunks, and the cache-off engine's greedy streams are the forward
  pass's argmax (``test_serving_oracle.served_equals_forward``). Sharing
  changes WHERE KV physically lives, never what gets sampled.
- **Zero copies**: hits install by referencing published block ids,
  retirement DONATES blocks instead of copying out.
- **Physical sharing**: concurrent holders of one prefix reference the
  SAME block ids (refcount >= 2, ``kv_blocks_shared`` gauge).
- **Compile-once survives paging**: block tables are runtime arguments;
  ``decode_compilations() == 1`` under any traffic mix.
- **Ownership discipline**: a mid-decode cancel frees the private tail
  but never the shared prefix; unref-to-zero returns a block to the
  heap exactly once; ``num_free`` is restored after an
  eviction-pressure + cancel storm.
"""
import numpy as np
import pytest

from paddle_tpu.serving import BlockManager, GenerationRequest, PagedKVCache

import serving_support
from serving_support import BS, clone as _clone
from test_metrics_prom import parse_prometheus
from test_serving_oracle import served_equals_forward


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=21)  # GQA: nkv=2 < nh=4


def _engine(model, prefix_cache=True, **kw):
    """The shared helper at this file's geometry: 64 positions and NO
    chunking (the engine's own 512-token chunk never triggers), because
    the tests below pin the whole-prompt and suffix programs' buckets."""
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("decode_chunk", 1)
    kw.setdefault("prefix_block_size", BS)
    return serving_support.engine_as_given(model, prefix_cache=prefix_cache,
                                           **kw)


_SYS = np.random.RandomState(7).randint(0, 256, (20,)).astype(np.int32)


def _req(tail_seed, n_tail=6, sys_prompt=_SYS, **kw):
    """Shared-system-prompt request: 20 shared tokens + a unique tail."""
    tail = np.random.RandomState(tail_seed).randint(
        0, 256, (n_tail,)).astype(np.int32)
    kw.setdefault("max_new_tokens", 6)
    return GenerationRequest(prompt=np.concatenate([sys_prompt, tail]), **kw)


def _reference_run(model, reqs, **kw):
    """The streams to expect: the cache-off engine's, whose greedy ones
    are first held to the forward pass (an oracle outside the serving
    code); a sampled stream is held by being the same with the cache on."""
    # (a pool of another size is another trace of the step program, and
    # the tests pin the engine under test at one: the support module keys
    # its caches by the pool's size)
    eng = _engine(model, prefix_cache=False, **kw)
    outs = [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]
    for r, out in zip(reqs, outs):
        if r.temperature <= 0:
            served_equals_forward(model, r.prompt, out)
    return outs


class TestTransparency:
    def test_streams_identical_greedy_and_sampled(self, model):
        """The acceptance pin: hit/miss mixes, greedy and seeded-sampled,
        stream the exact cache-off tokens with one decode compilation."""
        reqs = [_req(1), _req(2),
                _req(3, temperature=0.9, top_k=5, seed=123),
                _req(4, temperature=0.7, top_k=3, seed=9)]
        want = _reference_run(model, reqs)
        eng = _engine(model)
        got = [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]
        assert got == want
        pc = eng.prefix_cache
        assert pc.stats["hits"] >= 2           # later admissions reused
        assert pc.stats["donated_blocks"] > 0  # publish = adoption
        assert eng.decode_compilations() == 1
        # hits really skipped device prefill work
        assert eng.stats["prefill_tokens"] == \
            sum(len(r.prompt) for r in reqs) - pc.stats["hit_tokens"]

    @pytest.mark.slow  # 8 s chunk-boundary duplicate: test_streams_identical_
    # greedy_and_sampled above is the default paged rep (870s cap)
    def test_fused_chunks_cross_block_boundaries(self, model):
        """decode_chunk > block-crossing distance: fused ticks write
        across block boundaries through pre-grown tables; streams stay
        byte-identical and the step-size compile set stays the pow2
        ladder."""
        reqs = [_req(10, max_new_tokens=20), _req(11, max_new_tokens=20)]
        want = _reference_run(model, reqs, decode_chunk=8)
        eng = _engine(model, decode_chunk=8)
        got = [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]
        assert got == want

    def test_paged_without_prefix_cache(self, model):
        """The pool stands alone: sized to the live grid, no trie, same
        streams."""
        reqs = [_req(20), _req(21, temperature=0.8, top_k=4, seed=5)]
        want = _reference_run(model, reqs)
        eng = _engine(model, prefix_cache=False)
        got = [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]
        assert got == want
        assert eng.prefix_cache is None
        assert eng.cache.pool.num_blocks == 2 * (64 // BS)  # live grid
        assert eng.cache.pool.num_used == 0  # all returned at retirement

    @pytest.mark.slow  # eviction-pressure duplicate: the unified
    # engine's matrix pins evictions + byte-identical streams on the
    # default path (test_ragged_step) and the eviction-equality rep
    # stays default in test_prefix_cache
    def test_eviction_pressure_keeps_streams_exact(self, model):
        """A trie budget far smaller than the working set: evictions
        fire, live sequences always win the pool (evict-on-demand), and
        streams stay byte-identical."""
        reqs = [_req(30 + i, sys_prompt=np.random.RandomState(100 + i % 5)
                     .randint(0, 256, (16,)).astype(np.int32),
                     max_new_tokens=4) for i in range(10)]
        want = _reference_run(model, reqs)
        eng = _engine(model, prefix_blocks=3)
        pool = eng.prefix_cache.pool
        outs = []
        for r in reqs:  # serially, so pool pressure peaks per publish
            outs.append(eng.generate([_clone(r)])[0].tolist())
            assert pool.num_used <= pool.num_blocks
        assert outs == want
        assert eng.prefix_cache.stats["evictions"] > 0


class TestZeroCopySharing:
    def test_concurrent_hits_share_physical_blocks(self, model):
        """Two live sequences hitting the same chain REFERENCE the same
        physical blocks: their table prefixes are equal, the blocks
        carry refcount 2, and the kv_blocks_shared accounting sees them.
        Divergent tails still match the cache-off streams (writes land
        in private tail blocks)."""
        a = _req(31, max_new_tokens=8)
        b = _req(32, max_new_tokens=8, temperature=0.9, top_k=4, seed=3)
        want = _reference_run(model, [a, b])
        eng = _engine(model)
        eng.generate([_req(30, max_new_tokens=2)])  # publish the chain
        sa, sb = eng.submit(_clone(a)), eng.submit(_clone(b))
        step0 = eng.stats["steps"]
        seen_shared = False
        while eng.has_work():
            eng.step()
            if eng.stats["steps"] == step0 + 1:
                shared = set(n.block_id for n in sa.prefix_nodes) & \
                    set(n.block_id for n in sb.prefix_nodes)
                assert shared          # same physical blocks, no copies
                assert all(eng.prefix_cache.pool.refcount(bid) == 2
                           for bid in shared)
                assert eng.cache.pool.num_shared >= len(shared)
                # the tables literally point at the shared blocks
                ta = eng.cache.tables[sa.slot][:len(sa.prefix_nodes)]
                tb = eng.cache.tables[sb.slot][:len(sb.prefix_nodes)]
                assert set(ta) & set(tb) == shared
                seen_shared = True
        assert seen_shared
        assert [sa.tokens, sb.tokens] == want
        assert sa.prefix_hit_tokens == sb.prefix_hit_tokens == 2 * BS
        # pins drained at retirement; trie-resident blocks are zero-ref
        assert not eng.prefix_cache.pool._ref.any()

    def test_donated_blocks_are_adopted_not_copied(self, model):
        """Retirement hands the sequence's own prompt blocks to the
        trie: the next identical prompt's matched chain holds the SAME
        physical ids the first sequence's table held."""
        eng = _engine(model)
        s1 = eng.submit(_req(40, max_new_tokens=4))
        eng.step()
        assert s1.status == "running"
        # prompt = 26 tokens -> blocks 0..2 hold the 24 full-block rows
        first_blocks = [int(b) for b in eng.cache.tables[s1.slot][:3]]
        while eng.has_work():
            eng.step()
        matched = eng.prefix_cache.lookup(_req(40).prompt, record=False)
        assert [n.block_id for n in matched] == first_blocks
        assert eng.prefix_cache.stats["donated_blocks"] >= 3


class TestOwnershipDiscipline:
    def test_cancel_mid_decode_frees_tail_not_shared_prefix(self, model):
        """The COW-fork teardown: cancelling a hit mid-decode returns
        its PRIVATE tail blocks to the heap while the shared prefix
        (pinned by the trie + the surviving holder) stays resident, and
        the survivor's stream is untouched."""
        b = _req(51, max_new_tokens=10)
        want_b = _reference_run(model, [b])
        eng = _engine(model)
        eng.generate([_req(50, max_new_tokens=2)])  # publish the chain
        pool = eng.prefix_cache.pool
        used_baseline = pool.num_used
        sa = eng.submit(_req(52, max_new_tokens=30))
        sb = eng.submit(_clone(b))
        eng.step()
        eng.step()
        assert sa.status == "running"
        shared = [n.block_id for n in sa.prefix_nodes]
        assert shared and shared == [n.block_id for n in sb.prefix_nodes]
        tail = [blk for blk in eng.cache.slot_block_ids(sa.slot)
                if blk not in shared]
        assert tail                    # private suffix/decode blocks
        free_before = pool.num_free
        assert eng.cancel(sa)
        # the whole private tail went back to the heap... except blocks
        # the cancel's own publish donated (full prompt blocks beyond
        # the matched chain); either way every shared block survived
        for blk in shared:
            assert pool.refcount(blk) >= 1   # sb still pinning
            assert blk not in pool._free_set
        assert pool.num_free >= free_before
        while eng.has_work():
            eng.step()
        assert sb.tokens == want_b[0]  # bystander byte-identical
        assert not pool._ref.any()
        assert pool.num_used >= used_baseline  # trie chain still cached

    def test_eviction_and_cancel_storm_restores_num_free(self, model):
        """Mirrors the PR 2 slot-recovery tests at block granularity: a
        storm of admissions, cancels, and trie-eviction pressure ends
        with every live pin drained and the free count consistent (pool
        = free + trie-resident blocks)."""
        eng = _engine(model, prefix_blocks=2, num_slots=2)
        pool = eng.prefix_cache.pool
        rng = np.random.RandomState(3)
        live = []
        for i in range(12):
            sysp = np.random.RandomState(200 + i % 3).randint(
                0, 256, (16,)).astype(np.int32)
            tail = rng.randint(0, 256, (5,)).astype(np.int32)
            live.append(eng.submit(GenerationRequest(
                prompt=np.concatenate([sysp, tail]),
                max_new_tokens=int(rng.randint(2, 12)))))
            eng.step()
            if i % 3 == 2:            # cancel a random still-live seq
                cand = [s for s in live if not s.done]
                if cand:
                    eng.cancel(cand[int(rng.randint(len(cand)))])
        while eng.has_work():
            eng.step()
        assert not pool._ref.any()               # every pin drained
        assert eng.cache.num_free == eng.num_slots
        # allocated == trie-resident exactly; nothing leaked
        assert pool.num_used == eng.prefix_cache.num_cached_blocks
        assert pool.num_free == pool.num_blocks - pool.num_used
        assert eng.prefix_cache.stats["evictions"] > 0

    def test_live_growth_reclaims_trie_blocks_on_demand(self):
        """A dry pool with unpinned trie residents: ensure_capacity
        evicts them to feed live growth (live sequences always win the
        pool); pinned chains survive and a fully-pinned dry pool is a
        hard error, not a corruption."""
        from paddle_tpu.serving import PrefixCache
        pool = BlockManager(1, 4, 4, 1, 2)
        pc = PrefixCache(pool, max_blocks=2)
        cache = PagedKVCache(1, 1, 16, 1, 2, block_size=4, pool=pool,
                             prefix_cache=pc)
        b0, b1 = pool.alloc(), pool.alloc()
        donated = pc.publish_donate(np.arange(8), [b0, b1])
        assert donated == {b0, b1} and pc.num_cached_blocks == 2
        slot = cache.alloc()
        cache.ensure_capacity(slot, 16)     # needs all 4: 2 free + 2 evicted
        assert int(cache._n_blocks[slot]) == 4
        assert pc.num_cached_blocks == 0    # trie yielded on demand
        assert pool.num_free == 0
        cache.free(slot)
        assert pool.num_free == 4           # private tail fully returned
        # fully-pinned dry pool: allocation refuses loudly
        b2 = pool.alloc()
        pc.publish_donate(np.arange(100, 104), [b2])
        matched = pc.lookup(np.arange(100, 105))
        pc.acquire(matched)                 # live reader pins the chain
        for _ in range(3):
            pool.ref(pool.alloc())          # the rest is live-owned too
        slot = cache.alloc()
        with pytest.raises(RuntimeError, match="pool exhausted"):
            cache.ensure_capacity(slot, 4)

    def test_unref_to_zero_frees_exactly_once(self):
        """BlockManager.drop: the heap gets the block back exactly when
        the count hits zero — once. A second drop raises, a drop while
        other readers remain frees nothing."""
        pool = BlockManager(1, 2, 4, 1, 2)
        blk = pool.alloc()
        pool.ref(blk)
        pool.ref(blk)                  # two readers
        assert pool.drop(blk) is False  # one left; still allocated
        assert blk not in pool._free_set
        assert pool.drop(blk) is True   # zero: freed, exactly once
        assert blk in pool._free_set
        with pytest.raises(ValueError, match="below zero"):
            pool.drop(blk)
        assert pool.num_free == 2 - 1 + 1  # only one free event happened


class TestCompileDiscipline:
    @pytest.mark.slow  # compile-discipline duplicate: the unified
    # engine's hit/miss/eviction/cancel matrix (test_ragged_step),
    # chunked closed-compile-set (test_chunked_prefill) and the
    # engine-level request-mix closure (test_serving) stay the default
    # reps of the same decode_compilations()==1 chain
    def test_mixed_traffic_keeps_decode_at_one(self, model):
        """Waves of hits/misses/divergence leave decode_compilations()
        at 1 and the prefill/suffix compile set closed over the pow2
        grid — block tables are runtime data."""
        eng = _engine(model)

        def wave(e):
            outs = e.generate(
                [_req(60), _req(61),
                 _req(62, temperature=0.8, top_k=6, seed=2),
                 GenerationRequest(
                     prompt=np.random.RandomState(63).randint(
                         0, 256, (2 * BS,)).astype(np.int32),
                     max_new_tokens=3),
                 _req(64, n_tail=3)])
            return [o.tolist() for o in outs]

        first = wave(eng)
        second = wave(eng)
        assert second == first
        assert eng.decode_compilations() == 1
        prefill0 = eng.prefill_compilations()
        third = wave(eng)
        assert third == first
        assert eng.decode_compilations() == 1
        assert eng.prefill_compilations() == prefill0  # zero new traces


class TestMetricsSurface:
    def test_paged_gauges_strict_parsed(self, model):
        """/metrics carries kv_blocks_shared + kv_block_table_fill, all
        valid under the strict v0.0.4 parser."""
        from paddle_tpu.serving.server import ServingGateway
        eng = _engine(model, num_slots=2)
        gw = ServingGateway(eng, start=False)  # no driver thread needed
        eng.generate([_req(70, max_new_tokens=2)])   # publish the chain
        # two live holders of the shared chain at scrape time
        sa = eng.submit(_req(71, max_new_tokens=20))
        sb = eng.submit(_req(72, max_new_tokens=20))
        eng.step()
        fams = parse_prometheus(gw.registry.render())  # strict: raises

        def val(name):
            return fams[name]["samples"][(name, ())]

        assert fams["kv_blocks_shared"]["type"] == "gauge"
        assert val("kv_blocks_shared") == eng.cache.pool.num_shared >= 2
        assert fams["kv_block_table_fill"]["type"] == "gauge"
        assert 0.0 < val("kv_block_table_fill") <= 1.0
        assert val("kv_block_table_fill") == pytest.approx(
            eng.cache.table_fill())
        assert val("serving_prefix_cache_hits_total") >= 2
        assert val("kv_prefix_blocks") == eng.cache.pool.num_used
        eng.cancel(sa)
        eng.cancel(sb)
        while eng.has_work():
            eng.step()
        fams2 = parse_prometheus(gw.registry.render())
        assert fams2["kv_blocks_shared"]["samples"][
            ("kv_blocks_shared", ())] == 0
        assert fams2["kv_block_table_fill"]["samples"][
            ("kv_block_table_fill", ())] == 0.0

class TestConstruction:
    def test_pool_too_small_for_live_grid_rejected(self):
        pool = BlockManager(1, 3, BS, 1, 2)
        with pytest.raises(ValueError, match="cannot back"):
            PagedKVCache(1, 2, 64, 1, 2, block_size=BS, pool=pool)

    def test_shared_prefix_cache_geometry_validated(self, model):
        """A shared PrefixCache whose pool can't also hold the live
        block grid (or mismatches block size) fails fast at __init__."""
        from paddle_tpu.serving import PrefixCache
        live = 2 * (64 // BS)
        small = PrefixCache(BlockManager(4, live, BS, 2, 16))  # no headroom
        with pytest.raises(ValueError, match="cannot back|live blocks"):
            _engine(model, prefix_cache=small)
        paged_donor = _engine(model)
        ok = _engine(model, prefix_cache=paged_donor.prefix_cache)
        assert ok.prefix_cache is paged_donor.prefix_cache
        with pytest.raises(ValueError, match="geometry|does not match"):
            _engine(model, prefix_cache=paged_donor.prefix_cache,
                    prefix_block_size=BS * 2)

    def test_prefix_blocks_zero_rejected(self, model):
        with pytest.raises(ValueError, match="prefix_blocks"):
            _engine(model, prefix_blocks=0)

    def test_shared_dense_idiom_cache_gets_a_trie_budget(self, model):
        """Adopting a budget-less PrefixCache caps trie residency at the
        pool's headroom over the live grid — donations stay bounded."""
        from paddle_tpu.serving import PrefixCache
        live = 2 * (64 // BS)
        pc = PrefixCache(BlockManager(4, live + 3, BS, 2, 16))
        assert pc.max_blocks is None
        eng = _engine(model, prefix_cache=pc)
        assert eng.prefix_cache is pc and pc.max_blocks == 3
