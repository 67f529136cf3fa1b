"""Automatic prefix caching (serving/prefix_cache.py + block_manager.py):
block-granular KV reuse across requests sharing prompt prefixes.

The load-bearing properties:

- **Transparency**: token streams with the cache on are byte-identical
  to the cache-disabled engine — greedy AND seeded sampled — across
  hits, misses, evictions, and COW divergence. The cache changes WHERE
  prefix KV comes from (shared blocks + suffix prefill vs full prefill),
  never what gets sampled.
- **Compile-once survives caching**: mixed traffic keeps
  ``decode_compilations() == 1``; the prefill (cold + suffix) compile
  set is bounded by geometry, not traffic.
- **Ref-count lifecycle**: matched chains are pinned for the sequence
  lifetime, pins drain to zero at retirement, pinned blocks never
  evict, and pool occupancy never exceeds the pool.
- **LRU eviction** under the trie budget degrades hit-rate, never
  correctness.
"""
import collections
import zlib

import numpy as np
import pytest

from paddle_tpu.serving import BlockManager, GenerationRequest, PrefixCache

import serving_support
from serving_support import BS, clone as _clone
from test_metrics_prom import parse_prometheus


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
    if prefix_cache:
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


def _donate(pc, tokens, content=None):
    """Publish ``tokens`` the way a retiring slot does: its full blocks
    sit in private pool blocks, each holding the slot's ownership ref
    (allocated evict-on-demand like ``PagedKVCache._alloc_block``); the
    trie adopts the ones it does not hold yet and the rest go back to
    the heap. ``content(path)`` gives a block's buffers, when the test
    reads them back."""
    pool = pc.pool
    keys = pc._blocks_of(tokens, len(tokens))
    ids = []
    for i in range(len(keys)):
        block = pool.alloc()
        while block is None and pc._evict_one():
            block = pool.alloc()
        if block is None:       # all pinned: a live slot would be preempted
            break
        pool.ref(block)
        if content is not None:
            pool.write_block(block, content(tuple(keys[:i + 1])))
        ids.append(block)
    donated = pc.publish_donate(
        np.asarray(tokens)[:len(ids) * pc.block_size], ids)
    for block in ids:
        if block in donated:
            pool.unref(block)   # the trie adopted it
        else:
            pool.drop(block)
    return donated


def _cold_run(model, reqs, **kw):
    eng = _engine(model, prefix_cache=False, **kw)
    return [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]


class TestTransparency:
    def test_hit_stream_identical_greedy_and_sampled(self, model):
        """Requests sharing the system prompt: the later ones hit the
        published chain yet stream the exact cold-engine tokens —
        greedy and seeded-sampled both (same PRNG split walk)."""
        reqs = [_req(1), _req(2),
                _req(3, temperature=0.9, top_k=5, seed=123),
                _req(4, temperature=0.7, top_k=3, seed=9)]
        want = _cold_run(model, reqs)
        eng = _engine(model)
        got = [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]
        assert got == want
        pc = eng.prefix_cache
        assert pc.stats["hits"] >= 2          # later admissions reused
        assert pc.stats["hit_tokens"] >= 2 * BS
        assert eng.stats["prefill_tokens_saved"] == pc.stats["hit_tokens"]
        # hits really skipped device prefill work
        assert eng.stats["prefill_tokens"] == \
            sum(len(r.prompt) for r in reqs) - pc.stats["hit_tokens"]

    def test_full_block_prompt_leaves_final_token_uncovered(self, model):
        """A prompt that is an exact block multiple of a cached chain
        still prefills >= 1 token (the engine samples token 0 from the
        suffix logits): lookup never covers the final prompt token."""
        prompt = np.random.RandomState(40).randint(
            0, 256, (2 * BS,)).astype(np.int32)  # exactly 2 blocks
        reqs = [GenerationRequest(prompt=prompt, max_new_tokens=5),
                GenerationRequest(prompt=prompt.copy(), max_new_tokens=5)]
        want = _cold_run(model, reqs)
        eng = _engine(model)
        a = eng.generate([_clone(reqs[0])])[0]
        b = eng.generate([_clone(reqs[1])])[0]
        assert [a.tolist(), b.tolist()] == want
        # second run matched only 1 block: final block holds the last
        # prompt token, which must go through the suffix prefill
        assert eng.stats["prefill_tokens_saved"] == BS
        assert eng.prefix_cache.stats["hit_blocks"] == 1

    def test_cow_divergence_never_aliases(self, model):
        """Two concurrent sequences hitting the SAME cached chain then
        diverging (different tails, one sampled) match their solo runs:
        shared blocks are read-only and appends land in private tail
        blocks."""
        a = _req(31, max_new_tokens=8)
        b = _req(32, max_new_tokens=8, temperature=0.9, top_k=4, seed=3)
        want = _cold_run(model, [a, b])
        eng = _engine(model)
        eng.generate([_req(30, max_new_tokens=2)])  # publish the chain
        sa, sb = eng.submit(_clone(a)), eng.submit(_clone(b))
        step0 = eng.stats["steps"]
        while eng.has_work():
            eng.step()
            if eng.stats["steps"] == step0 + 1:
                # both admitted in one step, pinning the same blocks
                shared = set(n.block_id for n in sa.prefix_nodes) & \
                    set(n.block_id for n in sb.prefix_nodes)
                assert shared  # genuinely the same physical blocks
                assert all(eng.prefix_cache.pool.refcount(bid) == 2
                           for bid in shared)
        assert [sa.tokens, sb.tokens] == want
        assert sa.prefix_hit_tokens == sb.prefix_hit_tokens == 2 * BS
        # pins drained at retirement
        assert not eng.prefix_cache.pool._ref.any()


class TestEvictionAndBudget:
    def test_eviction_under_pressure_keeps_streams_exact(self, model):
        """A trie budget far smaller than the working set: evictions
        fire, the pool is never exceeded, streams stay byte-identical."""
        reqs = [_req(i, sys_prompt=np.random.RandomState(100 + i % 5)
                     .randint(0, 256, (16,)).astype(np.int32),
                     max_new_tokens=4) for i in range(10)]
        want = _cold_run(model, reqs)
        eng = _engine(model, prefix_blocks=3)
        pool = eng.prefix_cache.pool
        outs = []
        for r in reqs:  # serially, so pool pressure peaks per publish
            outs.append(eng.generate([_clone(r)])[0].tolist())
            assert pool.num_used <= pool.num_blocks
        assert outs == want
        assert eng.prefix_cache.stats["evictions"] > 0

    def test_pinned_blocks_never_evict_and_publish_degrades(self, model):
        """A chain pinned by a live sequence while the trie sits at its
        budget: other retirements donate, the trim reaps their blocks
        (degrade, not fail) and never the pinned chain."""
        eng = _engine(model, prefix_blocks=2, num_slots=2)
        pc = eng.prefix_cache
        eng.generate([_req(50, max_new_tokens=2)])   # fills the budget
        assert pc.num_cached_blocks >= 2
        held = _req(51, max_new_tokens=30)
        want_held = _cold_run(model, [held])[0]
        holder = eng.submit(_clone(held))            # pins the chain
        eng.step()
        assert len(holder.prefix_nodes) == 2
        pinned = [n.block_id for n in holder.prefix_nodes]
        # different prompts retire while the whole budget is pinned
        others = [GenerationRequest(
            prompt=np.random.RandomState(52 + i).randint(
                0, 256, (2 * BS,)).astype(np.int32), max_new_tokens=2)
            for i in range(3)]
        want = _cold_run(model, others)
        for other, tokens in zip(others, want):
            seq = eng.submit(_clone(other))
            while not seq.done:
                eng.step()
            assert seq.tokens == tokens
            assert [n.block_id for n in
                    pc.lookup(held.prompt, record=False)] == pinned
        # donations were adopted and trimmed back out; the pins stayed
        assert pc.stats["evictions"] >= 3
        assert pc.lookup(others[0].prompt, record=False) == []
        while eng.has_work():
            eng.step()
        assert holder.tokens == want_held            # never disturbed
        assert not pc.pool._ref.any()

    def test_same_step_cold_retirement_cannot_evict_pending_hit(self, model):
        """Regression: a cold sequence retiring INSIDE the admission
        group (max_new_tokens=1 donates into a full trie budget, and
        the trim evicts) must not evict the chain a same-step hit
        matched but hasn't installed yet — matched chains are pinned at
        lookup, before any cold admission runs."""
        sys16 = np.random.RandomState(55).randint(
            0, 256, (16,)).astype(np.int32)
        hit_req = GenerationRequest(
            prompt=np.concatenate([sys16, [5, 6, 7]]), max_new_tokens=6)
        cold_req = GenerationRequest(
            prompt=np.random.RandomState(56).randint(
                0, 256, (16,)).astype(np.int32), max_new_tokens=1)
        want_hit = _cold_run(model, [hit_req])[0]
        eng = _engine(model, prefix_blocks=2, num_slots=2)
        eng.generate([GenerationRequest(prompt=sys16, max_new_tokens=1)])
        pc = eng.prefix_cache
        assert pc.num_cached_blocks == 2            # chain fills the budget
        chain = [n.block_id for n in pc.lookup(hit_req.prompt, record=False)]
        cold_seq = eng.submit(_clone(cold_req))  # cold path admits first
        hit_seq = eng.submit(_clone(hit_req))
        while eng.has_work():
            eng.step()
        assert cold_seq.finish_reason == "length"
        assert hit_seq.tokens == want_hit        # chain survived intact
        assert hit_seq.prefix_hit_tokens == 2 * BS  # whole chain matched
        # the trim reaped the cold sequence's own donation, not the pins
        assert pc.stats["evictions"] >= 2
        assert pc.lookup(cold_req.prompt, record=False) == []
        assert [n.block_id for n in pc.lookup(hit_req.prompt, record=False)
                ][:2] == chain

    def test_lru_order_evicts_coldest_chain_first(self):
        """Unit-level: trie eviction picks the least-recently-touched
        zero-ref LEAF, keeping interior nodes reachable."""
        pool = BlockManager(1, 3, 4, 1, 2)
        pc = PrefixCache(pool)
        _donate(pc, np.arange(8))             # chain A: 2 blocks
        _donate(pc, np.arange(100, 104))      # chain B: 1 block
        assert pool.num_used == 3
        m = pc.lookup(np.arange(9))           # touch chain A (fresh tick)
        assert len(m) == 2
        _donate(pc, np.arange(200, 204))      # needs an eviction
        assert pc.stats["evictions"] == 1
        # B (coldest) died; A's chain still matches end to end
        assert len(pc.lookup(np.arange(9))) == 2
        assert pc.lookup(np.asarray([100, 101, 102, 103, 1])) == []


class TestCompileDiscipline:
    @pytest.mark.slow  # compile-discipline duplicate:
    # test_paged_attention's mixed-traffic test and
    # test_chunked_prefill's hit/miss/cancel/divergence matrix stay
    # the default reps
    def test_mixed_traffic_keeps_decode_at_one_and_prefill_bounded(
            self, model):
        """The acceptance pin: hits, misses, evictions, and a COW
        divergence leave ``decode_compilations() == 1``; once the
        bucket/group grid is warm a repeat wave adds ZERO prefill /
        suffix traces (the compile sets are closed over geometry, not
        traffic history)."""
        eng = _engine(model)  # ample pool: steady state

        def wave(e):
            outs = e.generate(
                [_req(60), _req(61),                       # hit pair
                 _req(62, temperature=0.8, top_k=6, seed=2),
                 GenerationRequest(                        # distinct miss
                     prompt=np.random.RandomState(63).randint(
                         0, 256, (2 * BS,)).astype(np.int32),
                     max_new_tokens=3),
                 _req(64, n_tail=3)])                      # divergence
            return [o.tolist() for o in outs]

        first = wave(eng)
        second = wave(eng)       # all-hit steady state; grid fully warm
        assert second == first   # caching is deterministic too
        assert eng.decode_compilations() == 1
        prefill0 = eng.prefill_compilations()
        third = wave(eng)
        assert third == first
        assert eng.decode_compilations() == 1
        assert eng.prefill_compilations() == prefill0   # zero new traces
        # eviction churn (budget of 4): hit patterns shift wave to wave as
        # blocks die, so new (group, bucket) combos may legitimately
        # appear — but only within the static pow2 grid. For this
        # traffic: cold prompts bucket to {16, 32}, suffixes to {8, 16},
        # groups to {1, 2} -> at most 4 cold + 4 suffix shapes total, vs
        # ~15 per wave if shapes leaked per-request. The smaller pool is
        # another shape of the step program: its own jit dict (the support
        # module keys its caches by the pool's size).
        eng2 = _engine(model, prefix_blocks=4)
        assert wave(eng2) == first
        assert wave(eng2) == first
        assert wave(eng2) == first
        assert eng2.prefix_cache.stats["evictions"] > 0
        assert eng2.decode_compilations() == 1
        assert eng2.prefill_compilations() <= 8


class TestMetricsSurface:
    def test_gateway_exposes_prefix_series_strict_parsed(self, model):
        """The gateway's /metrics body (registry.render IS the scrape
        body) carries hit/miss/eviction counters and the live
        kv_prefix_blocks gauge, valid under the strict v0.0.4 parser."""
        from paddle_tpu.serving.server import ServingGateway
        eng = _engine(model, prefix_blocks=3)
        gw = ServingGateway(eng, start=False)  # no driver thread needed
        for r in [_req(70), _req(71), _req(72)]:
            eng.generate([r])
        for i in range(4):  # distinct prompts: force evictions
            eng.generate([GenerationRequest(
                prompt=np.random.RandomState(80 + i).randint(
                    0, 256, (2 * BS,)).astype(np.int32),
                max_new_tokens=2)])
        fams = parse_prometheus(gw.registry.render())  # strict: raises

        def val(name):
            return fams[name]["samples"][(name, ())]

        assert fams["serving_prefix_cache_hits_total"]["type"] == "counter"
        assert val("serving_prefix_cache_hits_total") == \
            eng.prefix_cache.stats["hits"] >= 2
        assert val("serving_prefix_cache_misses_total") == \
            eng.prefix_cache.stats["misses"] >= 1
        assert val("serving_prefix_cache_evictions_total") == \
            eng.prefix_cache.stats["evictions"] >= 1
        assert val("serving_prefill_tokens_saved_total") == \
            eng.stats["prefill_tokens_saved"] > 0
        assert fams["kv_prefix_blocks"]["type"] == "gauge"
        assert val("kv_prefix_blocks") == eng.prefix_cache.pool.num_used
        assert val("kv_prefix_blocks_capacity") == \
            eng.prefix_cache.pool.num_blocks == 2 * (64 // BS) + 3
        assert val("serving_prefix_cached_blocks") == \
            eng.prefix_cache.num_cached_blocks <= 3
        # live gauge: occupancy changes move the next scrape
        before = val("kv_prefix_blocks")
        while eng.prefix_cache._evict_one():
            pass
        fams2 = parse_prometheus(gw.registry.render())
        assert fams2["kv_prefix_blocks"]["samples"][
            ("kv_prefix_blocks", ())] < before


class TestConstruction:
    def test_shared_cache_geometry_validated(self, model):
        """Passing another engine's PrefixCache with mismatched pool
        geometry fails fast at __init__, not mid-serving in XLA."""
        donor = _engine(model)
        # matching geometry: accepted
        ok = _engine(model, prefix_cache=donor.prefix_cache)
        assert ok.prefix_cache is donor.prefix_cache
        other = serving_support.model("llama", seed=5,
                                      hidden_size=32)      # head_dim 8
        with pytest.raises(ValueError, match="geometry"):
            _engine(other, prefix_cache=donor.prefix_cache)

    def test_prefix_blocks_zero_rejected_not_defaulted(self, model):
        with pytest.raises(ValueError, match="prefix_blocks must be >= 1"):
            _engine(model, prefix_blocks=0)


class TestTrieInvariantsRandomized:
    """ISSUE 16 satellite: randomized interleavings of donate /
    acquire / release / evict — with the host tier spilling and
    readmitting underneath — uphold the trie's structural invariants
    at every step:

    - no orphaned interior node (every resident node is reachable from
      the root with consistent parent/child links, and node count ==
      pool occupancy — nothing leaks, nothing aliases);
    - a pinned chain is never evicted (its nodes stay reachable while
      held);
    - refcounts equal the live pins exactly, and drain to zero;
    - the tier never exceeds its byte budget;
    - spill/readmit preserves block CONTENT: each published block
      carries a value derived from its full token path, and whatever
      is resident after any amount of churn still holds its path's
      exact bytes.
    """

    NB, BSU = 6, 4          # 6-block pool, 4-token blocks
    SHAPE = (1, 1, BSU, 1 * 2)  # one block: [L, 1, bs, Hkv * D]

    def _expected(self, path):
        v = float(zlib.crc32(repr(path).encode()) % 65536)
        return {"k": np.full(self.SHAPE, v, np.float32),
                "v": np.full(self.SHAPE, v + 0.5, np.float32)}

    def _check(self, pc, pool, held, content=False):
        nodes, stack = [], [(None, pc._root)]
        while stack:
            parent, children = stack.pop()
            for key, node in children.items():
                assert node.tokens == key          # key/identity agree
                assert node.parent is parent       # no orphaned interior
                nodes.append(node)
                stack.append((node, node.children))
        assert len(nodes) == pc._nodes == pool.num_used
        ids = [n.block_id for n in nodes]
        assert len(set(ids)) == len(ids)           # no block aliased
        want = collections.Counter()
        for chain in held:
            for n in chain:
                want[n.block_id] += 1
        for b in range(pool.num_blocks):
            assert pool.refcount(b) == want.get(b, 0)
        reachable = {id(n) for n in nodes}
        for chain in held:                         # pinned never evicted
            for n in chain:
                assert id(n) in reachable
        assert pc.tier.bytes_used <= pc.tier.capacity_bytes
        if content:
            for n in nodes:
                path = pc._path_of(n)
                got = pool.read_block(n.block_id)
                exp = self._expected(path)
                np.testing.assert_array_equal(got["k"], exp["k"])
                np.testing.assert_array_equal(got["v"], exp["v"])

    def test_random_interleavings_uphold_invariants(self):
        rng = np.random.RandomState(17)
        pool = BlockManager(1, self.NB, self.BSU, 1, 2)
        # tier budget of 4 blocks (64 B each): tier-side LRU trims and
        # descendant cascades fire too, not just spill/readmit
        pc = PrefixCache(pool, host_tier_bytes=4 * 64)
        # small alphabet + short lengths: prompts share prefixes often
        prompts = [rng.randint(0, 3, (int(n),)).astype(np.int32)
                   for n in rng.randint(4, 18, size=12)]
        held = []
        for step in range(150):
            op = rng.rand()
            prompt = prompts[rng.randint(len(prompts))]
            if op < 0.35:
                _donate(pc, prompt, content=self._expected)
            elif op < 0.65:
                m = pc.lookup(prompt)       # may readmit from the tier
                if m:
                    pc.acquire(m)
                    held.append(m)
            elif op < 0.9 and held:
                pc.release(held.pop(rng.randint(len(held))))
            else:
                pc._evict_one()
            self._check(pc, pool, held, content=(step % 10 == 9))
        # churn actually exercised every path
        assert pc.stats["evictions"] > 0
        assert pc.stats["spilled_blocks"] > 0
        assert pc.stats["readmitted_blocks"] > 0
        assert pc.stats["tier_evictions"] > 0      # tier LRU trimmed too
        # drain: release every pin, evict everything — refs to zero,
        # trie and pool empty, no stranded bookkeeping
        for chain in held:
            pc.release(chain)
        self._check(pc, pool, [], content=True)
        while pc._evict_one():
            pass
        assert pc._nodes == 0 and pool.num_used == 0
        assert not pool._ref.any()


class TestBlockManagerUnit:
    def test_alloc_free_ref_lifecycle(self):
        pool = BlockManager(1, 2, 4, 1, 2)
        a, b = pool.alloc(), pool.alloc()
        assert (a, b) == (0, 1) and pool.alloc() is None
        pool.ref(a)
        with pytest.raises(ValueError, match="refcount"):
            pool.free(a)                 # pinned blocks can't be freed
        assert pool.unref(a) == 0
        pool.free(a)
        with pytest.raises(ValueError, match="double-freed"):
            pool.free(a)
        with pytest.raises(ValueError, match="below zero"):
            pool.unref(b)
        assert pool.num_used == 1 and pool.num_free == 1
