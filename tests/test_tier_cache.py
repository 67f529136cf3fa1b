"""Tiered KV prefix cache (ISSUE 16): host-RAM spill tier behind
PrefixCache + the fleet cache plane (README "Tiered KV prefix cache").

The acceptance matrix:

- **Transparency**: token streams with the tier on are byte-identical
  to the tier-off engine AND to the cache-disabled engine — greedy and
  seeded-sampled — under eviction thrash that spills and readmits
  whole chains (the tier changes WHERE a hit's KV comes from, never
  what gets sampled). The int8-KV pool rides the same pin with its
  scale planes spilled and readmitted alongside.
- **Default-off**: ``host_tier_bytes=0`` constructs no tier, moves no
  bytes, and leaves every tier stat at zero — banked baselines cannot
  shift.
- **Compile-once**: the fetch/inject transfer pair is lru-cached per
  pool geometry (``kv_cache.tier_compilations``), readmission adds no
  jit keys, and ``decode_compilations() == 1`` holds through spill/
  readmit churn.
- **HostTier unit**: content-chained digests, LRU trim under the byte
  budget with descendant cascade (no unreachable orphans), oversize
  entries degrade to empty-never-over-budget.
- **Fleet cache plane**: a routed request about to miss on its replica
  pulls the spilled chain host-to-host from the sibling that evicted
  it (digest-addressed, by reference), the readmission is a local tier
  hit, the stream stays byte-identical, and the transfer shows up on
  ``/fleet/cacheplane``, ``/debug/fleet`` and the fleet metrics.
"""
import numpy as np
import pytest

from paddle_tpu.serving import (BlockManager, GenerationRequest, HostTier,
                                PrefixCache)
from paddle_tpu.serving.fleet import EngineFleet
from paddle_tpu.serving.kv_cache import tier_compilations

import serving_support
from serving_support import BS, CHUNK, clone as _clone
from test_metrics_prom import parse_prometheus
from test_serving_oracle import served_equals_forward

TIER = 1 << 24   # a generous host budget: LRU never trims in the legs


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=29)  # GQA: nkv=2 < nh=4


def _engine(model, **kw):
    """The shared helper, with a trie unless told otherwise."""
    kw.setdefault("prefix_cache", True)
    return serving_support.engine(model, **kw)


#: two 2-block system-prompt families; under a 2-block trie budget only
#: one family is resident at a time, so alternating them thrashes:
#: every family switch evicts (= spills) the other family's chain and
#: every return readmits it
_FAMS = [np.random.RandomState(200 + f).randint(
    0, 256, (2 * BS,)).astype(np.int32) for f in range(2)]


def _req(fam, tail_seed, **kw):
    tail = np.random.RandomState(tail_seed).randint(
        0, 256, (6,)).astype(np.int32)
    kw.setdefault("max_new_tokens", 6)
    return GenerationRequest(
        prompt=np.concatenate([_FAMS[fam], tail]), **kw)


def _thrash(rounds=3):
    """A/B/A/B...: one request per family per round, round 2 sampled."""
    reqs = []
    for i in range(rounds):
        for fam in (0, 1):
            kw = {}
            if i == 1:
                kw = dict(temperature=0.8, top_k=5,
                          seed=700 + 10 * fam + i)
            reqs.append(_req(fam, 10 * fam + i, **kw))
    return reqs


def _serial(eng, reqs):
    """One request at a time, so trie pressure peaks per publish and
    the spill/readmit order is deterministic."""
    return [eng.generate([_clone(r)])[0].tolist() for r in reqs]


# --------------------------------------------------------- transparency
class TestTierTransparency:
    def test_thrash_streams_equal_cold_engine_and_hits_recovered(
            self, model):
        """The headline pin: a 2-block trie budget thrashed by two
        alternating families. HBM-only forgets each evicted family; the
        tier readmits them (hits recovered) — and both stream the exact
        cache-disabled tokens, greedy and sampled, whose greedy ones are
        the forward pass's argmax."""
        reqs = _thrash()
        cold = _engine(model, prefix_cache=False)
        want = _serial(cold, reqs)
        for r, out in zip(reqs, want):
            if r.temperature <= 0:
                served_equals_forward(model, r.prompt, out)

        hbm = _engine(model, prefix_blocks=2)
        got_hbm = _serial(hbm, reqs)
        assert got_hbm == want
        assert hbm.prefix_cache.stats["tier_hits"] == 0

        eng = _engine(model, prefix_blocks=2,
                      host_tier_bytes=TIER)
        pc = eng.prefix_cache
        got = _serial(eng, reqs)
        assert got == want                     # transparency
        # the tier actually worked: spills fired, readmissions hit
        assert pc.stats["spilled_blocks"] > 0
        assert pc.stats["tier_hits"] > 0
        assert pc.stats["readmitted_blocks"] >= 2 * pc.stats["tier_hits"]
        # ... and recovered hits the HBM-only trie lost to eviction
        assert pc.stats["hits"] > hbm.prefix_cache.stats["hits"]
        assert pc.tier.bytes_used > 0
        # readmission re-allocates through the pool: budget never busts
        assert pc.pool.num_used <= pc.pool.num_blocks
        assert not pc.pool._ref.any()          # transient pins drained
        # compile-once survives spill/readmit churn
        assert eng.decode_compilations() == 2

    def test_paged_thrash_streams_identical(self, model):
        """Same pin on the paged default: donation-trim evictions spill,
        lookups readmit into the block-table install path."""
        reqs = _thrash()
        off = _engine(model, prefix_blocks=2)
        want = _serial(off, reqs)
        eng = _engine(model, prefix_blocks=2, host_tier_bytes=TIER)
        pc = eng.prefix_cache
        assert _serial(eng, reqs) == want
        assert pc.stats["spilled_blocks"] > 0
        assert pc.stats["readmitted_blocks"] > 0
        assert pc.stats["hits"] > off.prefix_cache.stats["hits"]
        assert eng.decode_compilations() == 2

    def test_int8_kv_tier_roundtrips_scale_planes(self, model):
        """The int8 pool's scale planes spill and readmit alongside the
        quantized KV (the PR-13 block-id-keyed layout, one tier entry),
        with streams byte-identical to the tier-off quantized engine."""
        reqs = _thrash()
        off = _engine(model, kv_dtype="int8", prefix_blocks=2)
        want = _serial(off, reqs)
        eng = _engine(model, kv_dtype="int8", prefix_blocks=2,
                      host_tier_bytes=TIER)
        pc = eng.prefix_cache
        assert _serial(eng, reqs) == want
        assert pc.stats["spilled_blocks"] > 0
        assert pc.stats["readmitted_blocks"] > 0
        # a resident tier entry carries all four planes
        with pc.tier._lock:
            bufs = next(iter(pc.tier._entries.values()))[0]
        assert set(bufs) == {"k", "v", "k_scale", "v_scale"}
        assert bufs["k"].dtype == np.int8
        assert bufs["k_scale"].dtype == np.float32
        assert eng.decode_compilations() == 2


# ----------------------------------------------------------- default off
class TestTierDefaultOff:
    def test_zero_budget_constructs_no_tier_and_moves_no_bytes(
            self, model):
        eng = _engine(model, prefix_blocks=2)
        pc = eng.prefix_cache
        assert pc.tier is None and pc.host_tier_bytes == 0
        _serial(eng, _thrash(rounds=2))
        assert pc.stats["evictions"] > 0       # thrash really evicted
        for key in ("spilled_blocks", "tier_hits", "readmitted_blocks",
                    "tier_evictions", "tier_transfers"):
            assert pc.stats[key] == 0, key

    def test_negative_budget_rejected(self, model):
        with pytest.raises(ValueError, match="host_tier_bytes"):
            PrefixCache(BlockManager(1, 2, 4, 1, 2), host_tier_bytes=-1)
        with pytest.raises(ValueError, match="host_tier_bytes"):
            _engine(model, host_tier_bytes=-5)


# -------------------------------------------------------- compile budget
class TestTierCompileDiscipline:
    def test_transfer_programs_bounded_by_geometry_not_traffic(
            self, model):
        """The fetch/inject pair is compile-once per (quantized, tp)
        pool geometry: a repeat thrash wave moves more blocks but adds
        ZERO tier traces (runtime-scalar block ids — python-int
        indexing would trace per block)."""
        eng = _engine(model, prefix_blocks=2, host_tier_bytes=TIER)
        reqs = _thrash(rounds=2)
        _serial(eng, reqs)
        n0 = tier_compilations()
        assert n0 >= 2          # >= one fetch + one inject trace
        spilled0 = eng.prefix_cache.stats["spilled_blocks"]
        _serial(eng, reqs)
        assert eng.prefix_cache.stats["spilled_blocks"] > spilled0
        assert tier_compilations() == n0       # zero new traces
        assert eng.decode_compilations() == 2


# ------------------------------------------------------- staging reuse
class TestStagingReuse:
    """ISSUE 20 satellite: spills used to land in freshly-allocated
    pageable numpy per block; they now land in the pool's per-shape
    staging buffers, recycled when a tier entry dies (trim / replace /
    readmission-inject). The pin is the allocation COUNT: one real
    ``np.empty`` per (shape, dtype), not one per spill."""

    def test_unit_one_allocation_per_shape_across_spill_cycles(self):
        pool = BlockManager(2, 4, 4, 1, 2)
        for cycle in range(5):
            for b in range(pool.num_blocks):
                bufs = pool.read_block(b)
                assert set(bufs) == {"k", "v"}
                pool.recycle_staging(bufs)      # entry died
        alloc = pool.staging.allocations
        assert alloc and all(n == 1 for n in alloc.values()), alloc

    def test_engine_thrash_allocates_once_per_shape(self, model):
        """A one-block tier budget under the thrash workload: every
        spill replaces (= recycles) the previous entry and every
        readmission injects-then-recycles, so dozens of spills draw on
        the per-shape steady state. The insert-then-trim window keeps
        at most TWO entries alive per plane (the incoming spill stages
        before the LRU victim recycles), so the pin is <= 2 buffers
        per plane ever allocated — and a repeat wave, spilling just as
        much again, allocates ZERO more (per shape, not per spill)."""
        probe = _engine(model, prefix_blocks=2)
        per_block = (probe.cache.pool.block_nbytes
                     + probe.cache.pool.scale_block_nbytes)
        eng = _engine(model, prefix_blocks=2,
                      host_tier_bytes=per_block)
        pc = eng.prefix_cache
        reqs = _thrash(rounds=3)
        _serial(eng, reqs)
        warm = dict(pc.pool.staging.allocations)
        spilled = pc.stats["spilled_blocks"]
        assert warm and all(n <= 2 for n in warm.values()), warm
        _serial(eng, reqs)
        assert pc.stats["spilled_blocks"] > spilled     # kept spilling
        assert pc.pool.staging.allocations == warm      # zero new

    def test_shared_entries_are_never_recycled(self):
        """The fleet cache plane holds exported buffers by reference:
        a shared entry's death must NOT hand its buffers to the
        recycler (the sibling tier would read the next spill's
        bytes)."""
        t = HostTier(capacity_bytes=64)
        recycled = []
        t.on_recycle = recycled.append
        own = {"k": np.full((64,), 1, np.uint8)}
        t.put(((1,),), own)
        # export marks shared; the replacement drop must skip recycle
        assert t.export_digest(HostTier.chain_digests(((1,),))[-1])
        t.put(((1,),), {"k": np.full((64,), 2, np.uint8)})
        assert recycled == []
        # the unshared replacement recycles normally when dropped
        t.put(((1,),), {"k": np.full((64,), 3, np.uint8)})
        assert len(recycled) == 1 and recycled[0]["k"][0] == 2


# ---------------------------------------------------------- HostTier unit
class TestHostTierUnit:
    def _bufs(self, fill, nbytes=64):
        return {"k": np.full((nbytes // 2,), fill, np.uint8),
                "v": np.full((nbytes // 2,), fill, np.uint8)}

    def test_chain_digests_content_only_and_incremental(self):
        a = [(1, 2, 3), (4, 5, 6), (7, 8, 9)]
        d = HostTier.chain_digests(a)
        assert len(d) == 3 and len(set(d)) == 3
        # two replicas that never exchanged state agree per depth
        assert HostTier.chain_digests(list(a)) == d
        # digest i depends on keys[:i+1] only (the prefix property)
        assert HostTier.chain_digests(a[:2]) == d[:2]
        assert HostTier.chain_digests([(9, 9, 9)] + a[1:])[0] != d[0]

    def test_put_pop_lru_and_descendant_cascade(self):
        t = HostTier(capacity_bytes=192)     # three 64-byte entries
        pa = ((1,),)
        pb = ((1,), (2,))                    # child of pa
        pc_ = ((3,),)                        # unrelated chain
        assert t.put(pa, self._bufs(1)) == 0
        assert t.put(pb, self._bufs(2)) == 0
        assert t.put(pc_, self._bufs(3)) == 0
        assert t.num_blocks == 3 and t.bytes_used == 192
        t.export_digest(HostTier.chain_digests(pc_)[-1])  # touch pc_
        # over budget: the LRU victim is pa — and evicting pa cascades
        # to pb (a spilled block with no resident/tier parent is
        # unreachable; keeping it would lie to the byte gauge)
        dropped = t.put(((4,),), self._bufs(4))
        assert dropped == 2
        assert not t.has(pa) and not t.has(pb)
        assert t.has(pc_) and t.has(((4,),))
        assert t.bytes_used == 128
        # pop removes (returning the shared flag alongside the
        # buffers — True here: export_digest handed out pc_'s
        # buffers by reference above); a second pop misses
        bufs, shared = t.pop(pc_)
        assert bufs["k"][0] == 3 and shared is True
        assert t.pop(pc_) is None
        assert t.export_digest("no-such-digest") is None

    def test_oversize_entry_degrades_to_empty_never_over_budget(self):
        t = HostTier(capacity_bytes=32)
        t.put(((1,),), self._bufs(1, nbytes=64))
        assert t.num_blocks == 0 and t.bytes_used == 0

    def test_replace_refreshes_bytes_not_duplicates(self):
        t = HostTier(capacity_bytes=1024)
        p = ((1,), (2,))
        t.put(p, self._bufs(1, nbytes=64))
        t.put(p, self._bufs(2, nbytes=128))
        assert t.num_blocks == 1 and t.bytes_used == 128
        assert t.pop(p)[0]["k"][0] == 2


# ------------------------------------------------------ fleet cache plane
class TestFleetCachePlane:
    def test_miss_on_a_hits_siblings_tier_byte_identical(self, model):
        """The distributed-prefix-cache pin: round-robin sends family A
        back to replica 1 AFTER replica 0 spilled A's chain — the fleet
        plane moves the chain host-to-host at submit, replica 1's
        admission readmits it as a local tier hit, and the stream is
        byte-identical to a cold single-engine run."""
        reqs = [_req(0, 50), _req(1, 60), _req(1, 61), _req(0, 51)]
        oracle = _engine(model, prefix_blocks=2)
        want = _serial(oracle, reqs)

        fl = EngineFleet(model, replicas=2, router="round-robin",
                         num_slots=2, max_seq_len=96,
                         prefix_block_size=BS, prefix_blocks=2,
                         prefill_chunk=CHUNK, max_queue=8,
                         host_tier_bytes=TIER, retry_backoff_s=0.0)
        try:
            got = []
            for r in reqs:     # serial: publishes land before the next
                st = fl.submit(_clone(r))  # route order: r0 r1 r0 r1
                got.append(st.result()[0].tolist())
            assert got == want
            doc = fl.cache_plane_doc()
            # family A's 2-block system chain moved r0 -> r1
            assert doc["transfers_total"] >= 2
            assert doc["transfer_bytes_total"] > 0
            rows = {r["replica"]: r for r in doc["replicas"]}
            assert rows[0]["enabled"] and rows[1]["enabled"]
            assert rows[0]["spilled_blocks"] >= 2      # the donor spilled
            assert rows[1]["tier_transfers_in"] >= 2   # the target pulled
            assert rows[1]["tier_hits"] >= 1           # ...and hit locally
            assert rows[1]["readmitted_blocks"] >= 2
            # /debug/fleet carries the cache-plane columns
            frow = [r for r in fl.fleet_table() if r["replica"] == 1][0]
            assert frow["tier_transfers_in"] >= 2
            # fleet metrics: one scrape covers the plane
            fams = parse_prometheus(fl.registry.render())
            s = fams["serving_fleet_tier_transfers_total"]["samples"]
            assert s[("serving_fleet_tier_transfers_total", ())] \
                == doc["transfers_total"]
            s = fams["serving_fleet_tier_transfer_bytes_total"]["samples"]
            assert s[("serving_fleet_tier_transfer_bytes_total", ())] \
                == doc["transfer_bytes_total"]
            # the peer direction landed on the target's tier ledger,
            # matching the fleet's byte total (r1 was the only puller)
            co = fl.replicas[1].gateway.cost
            assert co.tier_bytes("peer") == doc["transfer_bytes_total"]
        finally:
            fl.shutdown(drain=True, timeout=60)

    def test_plane_disabled_rows_when_tier_off(self, model):
        fl = EngineFleet(model, replicas=2, router="round-robin",
                         num_slots=2, max_seq_len=96,
                         prefix_block_size=BS, prefill_chunk=CHUNK,
                         max_queue=8, start=False)
        try:
            doc = fl.cache_plane_doc()
            assert doc["transfers_total"] == 0
            assert all(not r["enabled"] for r in doc["replicas"])
            # tier-off submits never touch the plane
            fl.start()
            st = fl.submit(_req(0, 70))
            st.result()
            assert fl.cache_plane_doc()["transfers_total"] == 0
        finally:
            fl.shutdown(drain=True, timeout=60)
