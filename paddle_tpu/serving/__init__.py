"""Continuous-batching serving subsystem (L7, SURVEY §3.5 / PAPERS.md).

Orca-style iteration-level scheduling on top of a block-table paged KV
cache: one compiled step program whose shapes depend only on
``(num_slots, token_budget)`` serves every request mix; requests are
admitted into free cache slots mid-flight, and a slot is freed the
moment its sequence hits EOS or its token budget — the ragged paged
attention kernel (``kernels/pallas_ragged_attention.py``) walks each
row's own length, so a freed block's stale rows cost no HBM traffic.

Public surface:

- :class:`GenerationRequest` / :class:`Sequence` — request & in-flight
  state (per-request deadlines via ``timeout_s``; ``finish_reason`` ∈
  :data:`FINISH_REASONS` = stop|length|cancelled|timeout|error)
- :class:`GenerationResult` — array-like generate() output + finish_reason
- :class:`PagedKVCache` — block-table paged attention: the
  :class:`BlockManager` pool IS the cache, slots address
  it through per-slot block tables, prefix hits are zero-copy
  references and retirement donates prompt AND generated blocks to the
  trie (README "Paged attention")
- :class:`FIFOScheduler` — admission + fused-chunk step policy +
  chunked-prefill token budgeting
- :class:`PriorityClass` / :class:`ClassTable` /
  :class:`PolicyScheduler` — multi-tenant SLO policy (README
  "Multi-tenant SLO serving"): priority classes with TTFT/TPOT
  targets, deadline-aware admission with per-class headroom and
  anti-starvation aging, SLO-driven preemption of lower-class work
  (engine ``priority_classes=...``; the default single class keeps
  the FIFO baseline byte-identical)
- :class:`ContinuousBatchingEngine` — the step-function serving API
  (``cancel()``, deadline sweeps, ``on_token``/``on_finish`` streaming
  hooks; ``prefix_cache=True`` turns on automatic prefix caching;
  ``prefill_chunk`` interleaves long cold-prompt prefills with decode
  steps to bound TTFT — README "Chunked prefill")
- :class:`BlockManager` / :class:`PrefixCache` — the block-granular
  prefix-cache subsystem: ref-counted KV block pool + hash-trie over
  prompt token blocks with LRU eviction (README "Automatic prefix
  caching")
- :class:`Drafter` / :class:`NgramDrafter` / :class:`ModelDrafter` —
  speculative-decode proposers (engine ``spec_decode=True``, README
  "Speculative decoding"): draft tokens verified as ragged spans
  through the paged kernel, rejected K/V rolled back by
  ``PagedKVCache.truncate``, streams byte-identical to speculation off

Fault tolerance (README "Fault tolerance & chaos testing"):
:class:`PoolExhausted` is the typed KV-pool-pressure signal the engine
repairs by preempting the youngest sequence (recompute, donated chain);
``engine.restore()`` re-enqueues a live sequence after a crash so the
supervised gateway driver can rebuild and continue streams
byte-identically; :mod:`.faults` is the deterministic fault-injection
harness (:class:`FaultPlan` / :class:`VirtualClock`) the chaos tests
drive.

Scale-out: :mod:`paddle_tpu.serving.fleet` (README "Engine fleet")
replicates the whole stack — N shared-nothing supervised engines
behind one routed front door with prefix-affinity routing,
failover-to-sibling on replica death, and live request migration
built on :meth:`ContinuousBatchingEngine.evict` + ``restore()``.

The HTTP layer on top lives in :mod:`paddle_tpu.serving.server`
(imported lazily — the engine has no HTTP dependency).
"""
from .block_manager import BlockManager
from .drafter import Drafter, ModelDrafter, NgramDrafter
from .engine import ContinuousBatchingEngine
from .faults import (FatalFault, FaultError, FaultPlan, TransientFault,
                     VirtualClock)
from .kv_cache import PagedKVCache, PoolExhausted
from .policy import ClassTable, PolicyScheduler, PriorityClass
from .prefix_cache import HostTier, PrefixCache
from .request import (FINISH_REASONS, GenerationRequest, GenerationResult,
                      Sequence)
from .scheduler import FIFOScheduler

__all__ = [
    "ContinuousBatchingEngine", "GenerationRequest", "GenerationResult",
    "Sequence", "PagedKVCache", "PoolExhausted",
    "FIFOScheduler", "FINISH_REASONS", "BlockManager", "PrefixCache",
    "HostTier", "PriorityClass", "ClassTable", "PolicyScheduler",
    "FaultPlan", "FaultError", "TransientFault", "FatalFault",
    "VirtualClock", "Drafter", "NgramDrafter", "ModelDrafter",
]
