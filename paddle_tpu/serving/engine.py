"""Continuous-batching decode engine (the Orca/vLLM serving loop on the
TPU decode path, SURVEY §3.5 / PAPERS.md).

One engine owns ``num_slots`` KV-cache slots over a block-table paged
pool and drives a step function: each :meth:`step` (1) admits queued
requests into free slots — one bucketed whole-prompt prefill per group,
or, for a prompt longer than ``prefill_chunk``, a slot claim whose
prompt then arrives chunk by chunk — then (2) runs ONE device program
over a packed token buffer holding every running slot's decode row and
this step's prefill chunks, then (3) retires sequences that hit EOS or
their token budget, freeing their slots for the next admission. Requests
join and leave the batch between any two steps, so short requests never
wait for long ones and the batch never restarts.

Compile discipline (the perf contract): the step program's shapes depend
only on ``(num_slots, packed size)``, and the unified step has two packed
sizes, chosen from what the step's plan holds: ``num_slots`` rounded up to
8 when it carries no prefill chunk, ``token_budget = num_slots +
prefill_chunk`` when it carries one. Block tables, span metadata,
per-request sampling knobs and per-slot ragged lengths are runtime
arrays. One compilation per packed size reached serves every request mix,
and neither is built before a step needs it —
:meth:`decode_compilations` counts traces so tests can pin this. Prefill
compiles once per (group, prompt-length) bucket.

Offline use::

    engine = ContinuousBatchingEngine(model, num_slots=8)
    outs = engine.generate([GenerationRequest(prompt=ids, ...), ...])

Online use: call :meth:`submit` at arrival time and :meth:`step` in a
loop; finished sequences come back from the step that retired them.
``model.generate()`` is a thin offline wrapper over this engine.

The unified step is a pipeline one program deep (README "Serving", "A
step is dispatched one ahead"): a :meth:`step` plans and dispatches
program j and only then fences program j-1 and accepts its tokens, so
the chip runs j while the host accepts, loops, admits and plans. A
decode row of j takes its input token from j-1's output on the device;
the plan counts the token in flight; a token is accepted only for the
sequence it was computed for; whatever changes slots outside plan ->
accept (cancel, evict, preemption, the pool repair, a deadline) fences
and accepts what is in flight first (``_drain``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.dsa import index_grid_params
from ..kernels.gated_delta_rule import state_shape as gdn_state_shape
from ..kernels.moe_ffn import STATS as MOE_STATS
from ..kernels.pallas_ragged_attention import ragged_grid_counts
from ..kernels.ssd import state_shape as ssd_state_shape
from ..profiler.tracing import NULL_SPAN
from .decode import attention_grid, \
    build_paged_suffix_prefill_fn, \
    build_prefill_fn, build_ragged_step_fn, TAUGHT_KEYS, latent_row_width
from .kv_cache import PagedKVCache, PoolExhausted
from .policy import ClassTable, PolicyScheduler, select_victims
from .request import GenerationRequest, GenerationResult, Sequence
from .scheduler import FIFOScheduler


#: why the pipeline was emptied (``serving_pipeline_drains_total{reason}``):
#: ``idle`` is a step with nothing to dispatch (the tail of the work, or
#: every row's finish already known); the others are events that change
#: slots outside plan -> accept; ``fault`` is a fence that raised (what was
#: in flight is dropped, not accepted)
DRAIN_REASONS = ("idle", "cancel", "evict", "preempt", "pool", "deadline",
                 "snapshot", "fault")

#: the rows (padded group x length bucket) of ONE whole-prompt prefill call:
#: a call's temporaries grow with its rows (a routed FFN's pair buffers are
#: rows x top_k x hidden in float32), so a larger burst of short prompts is
#: prefilled in several calls. 8,192 is the largest call an engine of up to
#: 32 slots makes of prompts up to 256 tokens; at 128 slots x 512 tokens one
#: call of 65,536 rows asked for 13.9 GiB beside the weights (PERF.md, PR 54)
WHOLE_PROMPT_ROWS = 8192


def program_stat(rows):
    """The ``stats`` key that counts the step programs fenced at a packed
    size of ``rows`` (``serving_step_programs_total{rows}``; an engine has
    one key for each of its :attr:`~ContinuousBatchingEngine.step_rows`)."""
    return "step_programs_%d" % rows


class _InFlight:
    """One dispatched unified step whose tokens the host has not read."""
    __slots__ = ("toks", "tok_fin", "moe", "keys_in", "n", "rows",
                 "chunks", "ahead", "packed", "size", "t_base")

    def __init__(self, toks, tok_fin, moe, keys_in, n, rows, chunks,
                 packed, size, t_base):
        self.toks, self.tok_fin, self.moe = toks, tok_fin, moe
        self.keys_in = keys_in      # the key state this step started from
        self.n = n                  # ticks it fused
        self.rows = rows            # [(slot, seq)]: its decode rows
        self.chunks = chunks        # [(slot, seq, offset, tokens, final)]
        self.packed = packed        # tokens in its packed buffer
        self.size = size            # rows of that buffer: the program's size
        # clock reading its cost counts from: the step() that dispatched
        # it into an empty pipeline; None when it went behind another
        # program (its cost then runs from that program's fence)
        self.t_base = t_base
        # what the NEXT plan must reckon with, by slot: (sequence, tokens
        # it will have gained when this step is accepted, whether its
        # next input token is this step's output)
        self.ahead = {slot: (seq, n, True) for slot, seq in rows}
        for slot, seq, _off, _ntok, final in chunks:
            if final:       # a restored sequence adopts no sampled token
                fresh = not seq.restore_point
                self.ahead[slot] = (seq, int(fresh), fresh)


class ContinuousBatchingEngine:
    """Slot-based continuous batching over a LLaMA-family model.

    The KV cache is block-table paged (:class:`~.kv_cache.PagedKVCache`,
    README "Paged attention"): the :class:`~.block_manager.BlockManager`
    pool IS the cache, every live slot addresses it through a per-slot
    block table (a runtime argument — ``decode_compilations()`` stays at
    one per packed size) and decode growth appends blocks lazily.
    ``prefix_block_size`` is the KV block size; the pool holds ``num_slots *
    ceil(max_seq_len / block_size)`` live blocks plus the
    ``prefix_blocks`` trie budget.

    Every step is ONE device program, the unified ragged step
    (``decode.build_ragged_step_fn`` over the ragged paged attention
    kernel, README "Unified ragged attention"): each slot contributes
    one variable-length query span (decode = span 1, prefill chunk =
    span n) to a packed token buffer of one of two sizes: ``num_slots``
    rows (rounded up to 8) when the step's plan holds no chunk,
    ``token_budget = num_slots + prefill_chunk`` when it holds one — the
    same body, specialised on the two shapes, each compiled when a step
    first needs it. ``decode_chunk`` bounds how many
    single-token ticks a pure-decode step fuses behind tick 0.

    ``prefill_chunk`` bounds TTFT under mixed traffic (README "Chunked
    prefill"): a prompt whose uncovered tail exceeds it is prefilled at
    most ``prefill_chunk`` tokens per step, as a span of the step
    program, K/V landing in the slot's own pool blocks at a host-side
    resume offset, so a long prompt never monopolizes a step while
    decode slots idle. Chunk boundaries are block-aligned (the value is
    rounded up to a block multiple); installed prefix-cache hits count
    toward the resume offset; cancellation or deadline expiry mid-chunk
    frees (or donates) the partial block chain. ``None``/``0`` disables
    chunking: every step then packs at the decode-only size. The per-step
    chunk grant is adapted at runtime from a measured tokens-per-second
    EWMA (the ``headroom`` stat): the engine grants roughly
    ``headroom_mult`` decode-steps' worth of tokens per step —
    ``prefill_chunk`` remains the hard cap — so chunk work throttles
    itself under decode load, where the decode baseline is measured on
    the program that carries the chunks (the multi-tick and speculative
    steps; the unified step runs its chunk-free steps at the smaller
    size, feeds no baseline and grants the cap: ``_prefill_budget``).
    ``headroom_mult=None`` pins the grant at the cap. ``step_clock``
    injects the timebase the EWMA reads (tests
    pass a virtual clock; default ``time.perf_counter``).

    ``prefix_cache=True`` enables automatic prefix caching
    (``serving/prefix_cache.py``): a retiring sequence DONATES its full
    prompt and generated blocks to a ref-counted LRU trie (ownership
    handoff, no copy), and a new admission whose prompt shares a cached
    block chain installs it by *referencing* the block ids in its table
    (N holders physically share one block) and prefills only the
    uncovered suffix. Trie-only blocks are reclaimed on demand when live
    growth needs them. Pass a :class:`~.prefix_cache.PrefixCache`
    instance to carry one pool across successive engines — ONLY when
    every engine is driven from the same single thread (the cache is
    lock-free by the engine's single-driver contract; two
    concurrently-stepping engines, e.g. two gateways, must not share
    one). Its pool geometry must match this engine's
    layers/heads/dtype/block size. ``prefix_blocks`` sizes the trie
    budget of the pool the engine builds itself (default: enough blocks
    to cache ``num_slots`` full-length prompts).

    ``spec_decode=True`` (default off) turns on speculative multi-token
    decode (README "Speculative decoding"): a :class:`~.drafter.Drafter`
    (default: model-free prompt-lookup n-grams,
    :class:`~.drafter.NgramDrafter`; or a tiny draft model via
    :class:`~.drafter.ModelDrafter`) proposes up to ``spec_k`` tokens
    per running slot, one batched forward scores all ``k + 1``
    positions per slot as a ragged span through the same paged
    attention kernel (draft K/V appended through the block tables
    exactly like a prefill chunk), the longest matching prefix is
    accepted — plus the model's own token at the first mismatch, so a
    launch always advances every slot — and rejected draft K/V rolls
    back via ``PagedKVCache.truncate`` (exact block accounting,
    donated/shared blocks untouched). Acceptance is exact-match
    against the target model's own sampling walk, so token streams are
    BYTE-IDENTICAL to ``spec_decode=False`` — greedy and seeded-
    sampled alike; speculation only reorders work. Prefill chunks ride
    the same one-launch-per-step program; drafts share the packed
    buffer's headroom with the chunk grant
    (``FIFOScheduler.spec_grants``). ``decode_compilations()`` counts
    the verify geometry and stays 1.

    ``decode_ticks > 1`` (default 1) turns on multi-tick decode (README
    "Multi-tick decode"): EVERY step runs ONE multi-tick program
    (``decode.build_multitick_step_fn``) whose packed tick 0 is the
    unified step verbatim and whose fused tail runs a RUNTIME number of
    decode ticks — up to ``decode_ticks`` — with on-device EOS/budget
    retirement (a finished row's appends drop inside the program
    exactly where the host's trim cuts) and early exit when every row
    retires. The host syncs once per block instead of once per token
    (``plan``/``launch``/``host-accept`` cover n tokens), trimming each
    slot at its first EOS/budget cut so streams stay byte-identical to
    ``decode_ticks=1``. The scheduler adapts the tick count per step
    (``FIFOScheduler.choose_decode_ticks``: 1 under mixed traffic,
    shrunk to the nearest guaranteed retirement while the queue waits);
    since the count is a runtime argument, ``decode_compilations()``
    stays at 1 — the multi-tick geometry keys its own jit-cache entry
    (``("mtick", num_slots, token_budget, decode_ticks, attn)``).
    Incompatible with ``spec_decode`` (a speculative step has no
    pure-decode tail to fuse). ``decode_chunk`` fusion is superseded on
    this path — the multi-tick program subsumes it with masking.

    Substrate note: a chunk-carrying step's packed buffer is a fixed
    ``num_slots + prefill_chunk`` tokens, which the TPU Pallas kernel
    prices at the LIVE spans only (its grid is a work list of the spans'
    query blocks, its KV walk ends at each row's length) but every dense
    matmul of every layer, and the CPU ``decode_attention="jnp"``
    oracle, compute in full — which is why a step with no chunk does not
    run at that size.
    """

    def __init__(self, model, num_slots=8, max_seq_len=None, decode_chunk=8,
                 prefill_bucketing="pow2", jit_cache=None,
                 prefix_cache=False, prefix_blocks=None,
                 prefix_block_size=32, prefill_chunk=512, headroom_mult=2.0,
                 step_clock=None, spec_decode=False, spec_k=4,
                 drafter=None, decode_ticks=1, kv_dtype=None,
                 quantize_weights=False, quantize_activations=False,
                 tp=1, collective_dtype="fp",
                 host_tier_bytes=0, priority_classes=None,
                 collective_overlap=False):
        c = model.config
        # multi-tenant SLO policy (README "Multi-tenant SLO serving"):
        # like host_tier_bytes, policy not geometry — classes change
        # admission order and preemption choices, never a traced shape
        # or a jit key. The default None is the single neutral class:
        # the plain FIFO scheduler is kept and every banked baseline
        # stays byte-identical.
        self.classes = ClassTable.coerce(priority_classes)
        self._policy = self.classes.active
        # host-RAM spill tier behind the prefix trie (README "Tiered KV
        # prefix cache"): policy, not geometry — it changes no traced
        # shape and adds no jit key, so it never joins a jit-cache or
        # fleet geometry tuple. 0 (default) = off, byte-identical to
        # every banked baseline.
        self._host_tier_bytes = int(host_tier_bytes)
        if self._host_tier_bytes < 0:
            raise ValueError(
                f"host_tier_bytes must be >= 0, got {host_tier_bytes}")
        if c.decode_attention not in ("pallas", "jnp"):
            raise ValueError(
                f"decode_attention must be 'pallas' or 'jnp', got "
                f"{c.decode_attention!r}")
        if prefill_bucketing not in ("pow2", "exact"):
            raise ValueError(
                f"prefill_bucketing must be 'pow2' or 'exact', got "
                f"{prefill_bucketing!r}")
        # multi-chip tensor parallelism (README "Tensor-parallel
        # serving"): tp=N shards every serving program over an N-device
        # heads-sharded mesh with the paged pool partitioned per shard.
        if int(tp) < 1:
            raise ValueError(f"tp must be >= 1, got {int(tp)}")
        if collective_dtype not in ("fp", "int8"):
            raise ValueError(
                f"collective_dtype must be 'fp' or 'int8', got "
                f"{collective_dtype!r}")
        self._tp = int(tp)
        # tp=1 has no mesh and no wire: normalize the collective dtype
        # so banners/geometry tuples report the effective value
        self._coll_dtype = collective_dtype if self._tp > 1 else "fp"
        if self._tp > 1:
            if c.num_attention_heads % self._tp \
                    or c.num_key_value_heads % self._tp:
                raise ValueError(
                    f"tp={self._tp} must divide num_attention_heads "
                    f"({c.num_attention_heads}) and num_key_value_heads "
                    f"({c.num_key_value_heads}): the mesh shards over "
                    f"heads")
            if self._coll_dtype == "int8" and c.hidden_size % self._tp:
                raise ValueError(
                    f"collective_dtype='int8' needs hidden_size "
                    f"({c.hidden_size}) divisible by tp={self._tp}: the "
                    f"quantized all-reduce chunks the activation per "
                    f"shard")
            from .decode import _tp_mesh
            # raises with the XLA_FLAGS hint when the mesh can't exist;
            # bound here so the pool construction below reuses THE mesh
            self._tp_mesh = _tp_mesh(self._tp)
        else:
            self._tp_mesh = None
        if kv_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"kv_dtype must be None (store KV at the pool dtype), "
                f"'int8' or 'fp8', got {kv_dtype!r}")
        if quantize_activations and not quantize_weights:
            raise ValueError(
                "quantize_activations=True requires "
                "quantize_weights=True: the int8xint8 projection path "
                "contracts runtime-quantized activations against the "
                "int8 weight pytree, so there is no activation-only "
                "variant")
        self.model = model
        self.config = c
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len or c.max_position_embeddings)
        self._bucketing = prefill_bucketing
        # the model brings its decode parameters; what the tree holds
        # chooses the layer body inside the programs (decode._decoder_layer)
        self._params, self._tied = model.decode_params()
        # a routed-FFN model may bring a record for the experts its step
        # programs pick (serving.routing_record): they then return them
        self._routing = getattr(model, "routing_record", None) \
            if "router" in self._params else None
        extras = [k for k in TAUGHT_KEYS if k in self._params]
        if extras:
            # only the default engine's two programs (whole-prompt prefill,
            # the unified step) were taught a layer with these entries:
            # every switch that runs another program raises, none falls back
            off = {"quantize_weights": bool(quantize_weights),
                   "quantize_activations": bool(quantize_activations),
                   "tp > 1": int(tp) > 1,
                   "decode_ticks > 1": int(decode_ticks) > 1,
                   "spec_decode": bool(spec_decode),
                   "decode_chunk > 1": int(decode_chunk) > 1,
                   "prefix_cache": bool(prefix_cache)}
            if any(k in self._params
                   for k in ("wkv_a", "linear_layers", "self_layers",
                             "ssd_layers", "mamba_layers", "window_layers")):
                # a latent pool has no heads to scale by and no V side:
                # the quantized pools' planes and kernels do not apply;
                # a quantized cache of a model with recurrent or window
                # layers would be their stores' to define as well, and
                # nothing defines it
                off["kv_dtype"] = kv_dtype is not None
            bad = [name for name, on in off.items() if on]
            if bad:
                raise ValueError(
                    f"{type(model).__name__} (a layer with "
                    f"{'/'.join(extras)}) is served by whole-prompt "
                    f"prefill and the unified ragged step only; these "
                    f"switches run a program with a layer body of its "
                    f"own that was not taught it: {', '.join(bad)}")
        # quantized KV pool (README "Quantized serving"): "int8" stores
        # int8 with per-row-per-head fp32 scale planes, "fp8" stores
        # float8_e4m3fn with per-BLOCK planes (constant 1.0 — e4m3's
        # exponent is the per-value scale; see BlockManager). Either
        # way the append paths quantize on write and the attention
        # kernels upcast in-register after the table-indirect DMA.
        # Default None keeps the pool at the model dtype — every banked
        # baseline is byte-identical to before the knob existed.
        # _kv_quant carries the MODE (falsy None / "int8" / "fp8"): the
        # builders and _pool_pspec dispatch on the string.
        self._kv_quant = kv_dtype
        self._kv_dtype = kv_dtype
        # int8 weight-only decode matmuls: convert ONCE per model (the
        # converted pytree is model-resident, so the factory's rebuilds
        # and every fleet replica share both the quantized arrays and
        # the jit cache — decode_compilations()==1 across rebuilds)
        self._wq8 = bool(quantize_weights)
        # int8xint8 decode projections (README "Quantized serving"):
        # activations quantize per-row at runtime and contract against
        # the int8 weights with int32 accumulate — the per-layer weight
        # DEQUANT disappears from the scanned layer body (the AST pin
        # in tests/test_cost_observatory.py holds it there). Default
        # False keeps the weight-only path byte-identical.
        self._a8 = bool(quantize_activations)
        if self._wq8:
            from .decode import quantize_decode_params
            qp = model.__dict__.get("_decode_qparams")
            if qp is None:
                qp = quantize_decode_params(self._params, self._tied)
                model.__dict__["_decode_qparams"] = qp
            self._params = qp
        # jit-key variant tags: quantized pools/params are a DIFFERENT
        # TRACE of the same impl (dtype / pytree structure), so engines
        # differing only in kv_dtype or quantize_weights sharing one
        # jit_cache dict must key apart or both compile pins break.
        # Appended at the END of each key; () on default engines keeps
        # every pre-existing key byte-identical. The TP degree (and its
        # collective dtype) is a variant the same way: a sharded
        # program is a different trace of the same impl, so tp=2 and
        # tp=1 engines sharing one jit_cache must key apart.
        self._kvtag = (("kv8f",) if self._kv_dtype == "fp8"
                       else ("kv8",) if self._kv_quant else ())
        self._wtag = ("w8",) if self._wq8 else ()
        self._atag = ("a8",) if self._a8 else ()
        self._tptag = ((f"tp{self._tp}", self._coll_dtype)
                       if self._tp > 1 else ())
        if self._tp > 1:
            # commit the params onto the mesh ONCE per (model, tp, w8):
            # rebuilds and fleet replicas share the placed arrays (and
            # the jit cache never pays a per-call reshard)
            from .decode import place_tp_params
            placed = model.__dict__.setdefault("_tp_params", {})
            pkey = (self._tp, self._wq8)
            if pkey not in placed:
                placed[pkey] = place_tp_params(self._params, self._tp,
                                               self._wq8)
            self._params = placed[pkey]
        dtype = self._params["embed"].dtype
        # layers with a recurrent state (a hybrid model's linear layers):
        # their cache is a store by slot beside the pool
        # (``PagedKVCache.state``), and the pool holds rows for the OTHER
        # layers only
        self._stateful = any(k in self._params for k in (
            "linear_layers", "self_layers", "ssd_layers", "mamba_layers",
            "window_layers"))
        kv_layers = c.num_kv_layers if self._stateful \
            else c.num_hidden_layers
        # what a cached token's row is: Hkv heads of head_dim on a K and a
        # V side, or for latent attention ONE row of the normalised latent
        # and the rotated shared key, padded to whole lanes, and no V side
        geom = dict(num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim)
        if "wkv_a" in self._params:
            geom = dict(num_kv_heads=1, v_dim=0, head_dim=latent_row_width(
                c.kv_lora_rank, c.qk_rope_head_dim))
        if "idx_layer" in self._params:
            # attention over a learned selection: the V side is the second
            # per-token cache, an index key a layer that has an indexer
            geom.update(v_dim=c.dsa.dim, v_layers=c.dsa.layers)
        if "window_layers" in self._params:
            # keys wider than values: the V side has a row of its own
            geom["v_dim"] = c.num_key_value_heads * c.v_head_dim
        from .block_manager import BlockManager
        from .prefix_cache import PrefixCache
        self.prefix_cache = None
        bs = int(prefix_block_size)
        if bs < 1:
            raise ValueError(
                f"prefix_block_size must be >= 1, got {bs}")
        max_blocks = -(-self.max_seq_len // bs)
        live = self.num_slots * max_blocks
        # the pool's STORAGE dtype follows kv_dtype (int8 data +
        # scale planes), not the model dtype — a shared pool must
        # match the engine's quantization mode exactly
        store = (jnp.float8_e4m3fn if self._kv_dtype == "fp8"
                 else jnp.int8 if self._kv_quant else dtype)
        # TP partitions the pool's HEAD axis across the mesh: the
        # BlockManager commits its arrays with that sharding once,
        # so every sharded program adopts them zero-copy
        tp_mesh = self._tp_mesh
        if isinstance(prefix_cache, PrefixCache):
            pool = prefix_cache.pool
            want = (c.num_hidden_layers, c.num_key_value_heads,
                    c.head_dim)
            have = (pool.k.shape[0], pool.num_kv_heads, pool.head_dim)
            if have != want or pool.k.dtype != store \
                    or pool.block_size != bs \
                    or getattr(pool, "kv_dtype",
                               None) != self._kv_dtype:
                raise ValueError(
                    f"shared PrefixCache pool geometry "
                    f"{have}/bs={pool.block_size}/{pool.k.dtype} does "
                    f"not match this engine "
                    f"{want}/bs={bs}/{store} "
                    f"(kv_dtype={self._kv_dtype!r})")
            if getattr(pool, "tp", 1) != self._tp:
                raise ValueError(
                    f"shared PrefixCache pool is partitioned for "
                    f"tp={getattr(pool, 'tp', 1)} but this engine "
                    f"runs tp={self._tp}: a pool's head-axis "
                    f"sharding must match every engine serving "
                    f"from it")
            if pool.num_blocks <= live:
                raise ValueError(
                    f"shared pool of {pool.num_blocks} blocks cannot "
                    f"back {live} live blocks plus a prefix trie")
            if prefix_cache.max_blocks is None:
                # a cache built without a budget: bound trie residency
                # to the pool's headroom over the live grid, else
                # donations grow until every decode-growth alloc pays
                # an eviction
                prefix_cache.max_blocks = pool.num_blocks - live
            self.prefix_cache = prefix_cache
        elif prefix_cache:
            if prefix_blocks is None:
                budget = self.num_slots * max(self.max_seq_len // bs, 1)
            else:
                budget = int(prefix_blocks)
                if budget < 1:
                    raise ValueError(
                        f"prefix_blocks must be >= 1, got {budget}")
            pool = BlockManager(
                kv_layers, live + budget, bs, dtype=dtype,
                kv_dtype=self._kv_dtype, mesh=tp_mesh, **geom)
            self.prefix_cache = PrefixCache(
                pool, max_blocks=budget,
                host_tier_bytes=self._host_tier_bytes)
        else:
            pool = BlockManager(
                kv_layers, live, bs, dtype=dtype,
                kv_dtype=self._kv_dtype, mesh=tp_mesh, **geom)
        state_geometry = window_geometry = None
        if "linear_layers" in self._params:
            g = c.gdn
            state_geometry = (c.num_linear_layers,
                              gdn_state_shape(g.heads, g.dk, g.dv),
                              g.conv - 1, c.conv_channels)
        elif "ssd_layers" in self._params:
            d = c.ssd
            state_geometry = (c.num_units,
                              ssd_state_shape(d.heads, d.head_dim, d.groups,
                                              d.state),
                              d.conv - 1, c.conv_channels)
        elif self._stateful and "window_layers" not in self._params:
            # Mamba-1 layers: a decoder-hybrid-decoder model's, which also
            # has window layers (below), or a tree with ``mamba_layers``
            state_geometry = (c.num_ssm_layers,
                              (c.mamba_d_state, c.d_inner),
                              c.mamba_d_conv - 1, c.d_inner)
        self._ring_blocks = 0
        if "self_layers" in self._params or "window_layers" in self._params:
            # a window layer's ring a slot: the window, the longest span a
            # step may write before it attends (a chunk; one token without
            # chunking) and a block, in whole blocks: no key a query of the
            # step may see is overwritten by the step's own rows
            span = int(prefill_chunk) if prefill_chunk else 1
            self._ring_blocks = min(
                max_blocks, -(-(c.sliding_window + -(-span // bs) * bs
                                + bs - 1) // bs))
            window_geometry = (c.num_window_layers, self._ring_blocks)
            if "window_layers" in self._params:
                # the window layers' own row: their KV heads, a key and a
                # value of different widths (the pool's row is the full
                # layers')
                window_geometry += (
                    c.swa_num_key_value_heads * c.head_dim,
                    c.swa_num_key_value_heads * c.v_head_dim)
        self.cache = PagedKVCache(
            kv_layers, self.num_slots, self.max_seq_len,
            geom["num_kv_heads"], geom["head_dim"], dtype=dtype,
            block_size=bs, pool=pool, prefix_cache=self.prefix_cache,
            kv_dtype=self._kv_dtype, state_geometry=state_geometry,
            window_geometry=window_geometry)
        # chunked prefill: the chunk is rounded UP to a block multiple so
        # every non-final chunk boundary is block-aligned: a partially
        # prefilled prompt is exactly a prefix of whole pool blocks + a
        # host resume offset, which keeps mid-prefill
        # cancellation/donation trivial.
        self._chunk = None
        if prefill_chunk and int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 (or None/0 to disable), "
                f"got {int(prefill_chunk)}")
        if prefill_chunk:
            self._chunk = -(-int(prefill_chunk) // bs) * bs
        # size the packed token buffer once — num_slots decode rows plus
        # the chunk cap, but only when a prompt long enough to chunk can
        # exist at all (a chunk cap >= max_seq_len can never trigger, so
        # the buffer stays num_slots and a decode-only engine pays
        # nothing for the chunk rows)
        chunkable = self._chunk is not None and self._chunk < self.max_seq_len
        self._token_budget = self.num_slots + (self._chunk if chunkable
                                               else 0)
        # the unified step packs at one of two sizes, chosen from what the
        # step's plan holds (``_unified_step``): the token budget when the
        # plan has a chunk row, else the decode rows alone, a whole sublane
        # group of 8, so that a decode-only step multiplies no chunk rows
        # nobody reads
        self._decode_rows = -(-self.num_slots // 8) * 8
        # speculative decode (rollback truncates the block tail; README
        # "Speculative decoding"): every step becomes ONE draft-extended
        # verify launch whose packed buffer shares its
        # headroom between prefill-chunk tokens and verify spans (a
        # verify span spends 1 + k positions of it). The buffer is
        # sized for the LARGER of the two demands, not their sum —
        # chunk-heavy steps throttle drafts, decode-heavy steps have
        # the chunk headroom to speculate into.
        self._spec = bool(spec_decode)
        if self._spec and int(spec_k) < 1:
            raise ValueError(f"spec_k must be >= 1, got {int(spec_k)}")
        self._spec_k = int(spec_k)
        self._spec_len = self._spec_k + 1       # the sampling-walk depth
        self._spec_budget = self.num_slots + max(
            self._chunk if chunkable else 0,
            self.num_slots * self._spec_k)
        self.drafter = None
        if self._spec:
            if drafter is None:
                from .drafter import NgramDrafter
                drafter = NgramDrafter()
            self.drafter = drafter
        # multi-tick decode (README "Multi-tick decode"): when > 1, the
        # engine runs EVERY step through ONE multi-tick program —
        # chunk rows ride tick 0 exactly like the unified step, and
        # pure-decode steps fuse up to decode_ticks on-device ticks
        # behind a single host sync, with EOS/budget retirement masked
        # inside the program (decode.build_multitick_step_fn). The tick
        # count actually run is a RUNTIME argument chosen per step by
        # the scheduler (FIFOScheduler.choose_decode_ticks: clamped to
        # 1 under mixed traffic, shrunk to the nearest guaranteed
        # retirement when the queue has waiting work), so one
        # compilation serves every tick count.
        if int(decode_ticks) < 1:
            raise ValueError(
                f"decode_ticks must be >= 1, got {int(decode_ticks)}")
        self._decode_ticks = int(decode_ticks)
        self._mtick = self._decode_ticks > 1
        # the packed size of the program that carries chunks, and every
        # size this engine's step programs can have, ascending
        # (``serving_step_programs_total{rows}``): the speculative and the
        # multi-tick step keep one
        self._chunk_rows = self._spec_budget if self._spec \
            else self._token_budget
        if self._spec or self._mtick:
            self._step_rows = (self._chunk_rows,)
        else:
            self._step_rows = tuple(sorted(
                {self._decode_rows}
                | ({self._token_budget} if chunkable else set())))
        if self._mtick and self._spec:
            raise ValueError(
                "decode_ticks > 1 is incompatible with spec_decode: a "
                "speculative step is a verify launch every step, so "
                "there is no pure-decode tail to multi-tick. spec_decode "
                "composes with: prefix_cache, "
                "prefill_chunk, kv_dtype, quantize_weights, "
                "quantize_activations, tp, collective_overlap, "
                "host_tier_bytes, priority_classes. decode_ticks > 1 "
                "composes with the same — pick one of the two step "
                "shapes")
        # TP compute/collective overlap (README "Collective overlap"):
        # the per-layer all-reduce pair (post o-proj + post down-proj
        # tp_reduce sites) switches to a chunked reduce-scatter /
        # all-gather schedule so chunk k's wire time hides behind chunk
        # k+1's compute. Same bits on the wire format (EQuARX int8
        # preserved) and same ledger bytes — but a DIFFERENT trace
        # (ppermute chains instead of one psum), so the tp tag grows an
        # "ov" marker to key overlap engines apart in a shared cache.
        self._coll_overlap = bool(collective_overlap)
        if self._coll_overlap and self._tp <= 1:
            raise ValueError(
                "collective_overlap=True requires tp > 1: the overlap "
                "schedule rewrites the per-layer tensor-parallel "
                "all-reduce pair, and a tp=1 engine has no collectives "
                "to overlap")
        if self._coll_overlap:
            self._tptag = self._tptag + ("ov",)
        if headroom_mult is not None and float(headroom_mult) <= 0:
            raise ValueError(
                f"headroom_mult must be > 0 (or None for fixed-cap chunk "
                f"pacing), got {headroom_mult}")
        self._headroom_mult = (None if headroom_mult is None
                               else float(headroom_mult))
        self._clock = step_clock if step_clock is not None \
            else time.perf_counter
        # the current step's start reading of step_clock: SLO stamps
        # (t_admitted/t_first_token/t_finish) quantize to it instead of
        # reading the clock again — step() must read its clock exactly
        # twice per step (start + end), a contract the deterministic
        # benches and the injected-tick-clock tests rely on. Step
        # granularity is exactly the resolution those latencies have.
        self._stamp_t = None
        # headroom EWMAs (the adaptive chunk budget's inputs): measured
        # unified-step tokens/second, and the duration of decode-only
        # steps (the latency baseline chunk work must not stretch past
        # ~headroom_mult x)
        self._tps_ewma = None
        self._dt_decode_ewma = None
        if self._policy:
            # clock + slot ledger bound late: the closures read the
            # live attributes at decision time, so the injected
            # step_clock and rebuilt slot arrays are always current
            self.scheduler = PolicyScheduler(
                decode_chunk, table=self.classes,
                clock=lambda: self._clock(),
                slot_usage=self._class_slot_usage)
        else:
            self.scheduler = FIFOScheduler(decode_chunk)
        self._slots = [None] * self.num_slots
        self._last_tok = np.zeros(self.num_slots, np.int32)
        self._keys = jnp.zeros((self.num_slots, 2), jnp.uint32)
        # the unified step's pipeline (module docstring): the program
        # dispatched and not yet fenced, the zeros a step with nothing in
        # flight passes as ``prev_toks``, the clock reading of the last
        # fence, what the current step() fenced (tokens, chunk tokens),
        # and sequences a drain outside step() finished (the next step()
        # returns them)
        self._inflight = None
        self._no_toks = jnp.zeros((self.num_slots,), jnp.int32)
        if self._tp_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self._tp_mesh, PartitionSpec())
            self._keys = jax.device_put(self._keys, rep)
            self._no_toks = jax.device_put(self._no_toks, rep)
        self._t_fence = None
        self._fenced = (0, 0)
        self._finished_outside = []
        # the step's open ``sweep`` and ``retire`` spans (None with tracing
        # off, and between steps): ``sweep`` is closed by whichever mark
        # comes first, ``admit`` or a variant's ``plan``; ``retire`` by the
        # reading that closes ``step``
        self._sweep = None
        self._retire_span = None
        # jitted programs, shareable across engines of the same model so
        # a fresh engine never re-traces (model.generate passes the
        # model-level dict)
        self._jit = jit_cache if jit_cache is not None else {}
        self.stats = {"steps": 0, "decode_calls": 0, "decode_steps": 0,
                      "slot_steps": 0, "active_slot_steps": 0,
                      "prefills": 0, "prefill_tokens": 0,
                      "prefill_tokens_saved": 0,
                      "prefill_chunks": 0, "chunk_tokens": 0,
                      "step_prefill_tokens": 0, "step_decode_tokens": 0,
                      "unified_steps": 0, "steps_dispatched_ahead": 0,
                      **{program_stat(r): 0 for r in self._step_rows},
                      **{"drains_" + r: 0 for r in DRAIN_REASONS},
                      "mtick_syncs": 0, "mtick_ticks": 0,
                      "last_decode_ticks": 0,
                      "spec_steps": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_tokens": 0,
                      "spec_last_accept": [],
                      "headroom": self._chunk or 0, "headroom_tps": 0.0,
                      "last_step_duration_s": 0.0, "last_step_tokens": 0,
                      "tokens_generated": 0, "cancelled": 0, "timeouts": 0,
                      "preemptions": 0, "restores": 0,
                      "policy_preemptions": 0,
                      "moe_pairs": 0, "moe_experts_touched": 0,
                      "moe_max_expert_pairs": 0, "moe_picks": 0,
                      "moe_compact_calls": 0, "moe_layer_calls": 0,
                      "state_rows": 0, "state_restarts_fault": 0,
                      "state_restarts_preempt": 0}
        # fault-injection hook (serving/faults.py): called with the
        # engine at the top of every step attempt; None in production.
        # Whatever it raises propagates to the driver — except
        # PoolExhausted, which the step loop repairs by preemption.
        self.fault_hook = None
        # request-lifecycle tracer (profiler/tracing.py, README
        # "Tracing & debugging"): None in production; the gateway
        # installs one (and re-installs it on every rebuilt engine).
        # Every instrumentation site guards on _tr() — one attribute
        # check when tracing is off, so the hot path pays nothing.
        self.tracer = None
        # device-boundary cost observatory (profiler/cost.py, README
        # "Cost attribution & /debug/profile"): None in production
        # engines built bare; the gateway installs ONE observatory
        # across every engine incarnation, so its dispatch/transfer/
        # compile counts stay monotonic across rebuilds. Every touch
        # guards on _co() — the tracer's one-attribute discipline.
        self.cost = None
        # the driver thread's phase clock (profiler/driver_clock.py): the
        # gateway hands its one instance to every engine incarnation; an
        # engine driven without a gateway has none, and :meth:`_mark` pays
        # one attribute check for it.
        self.driver_clock = None
        # streaming hooks (the gateway's wire into the step loop):
        # on_token(seq, token_id) fires for EVERY generated token the
        # moment the host sees it; on_finish(seq) fires exactly once per
        # sequence, for every finish_reason — including cancel(), whose
        # retirements never appear in a step() return. Both run on the
        # thread driving step() — keep them cheap and non-reentrant.
        self.on_token = None
        self.on_finish = None
        # policy-preemption hook: on_policy_preempt(victim_seq) fires
        # just before an SLO-driven displacement (the gateway's per-
        # victim-class counter). Same thread/cheapness contract as
        # on_token/on_finish. None on a policy-off engine — the step
        # loop never consults policy there.
        self.on_policy_preempt = None
        # step-cost hook: on_step(duration_s) fires once per step program
        # FENCED, where :meth:`_count_step` books it (inside step(), or
        # at a drain outside one), so a consumer (the gateway's
        # ``serving_step_duration_seconds``) counts programs, not calls
        self.on_step = None

    # ------------------------------------------------------------- tracing
    def _tr(self):
        """The active tracer, or None — THE guard every trace site
        uses, so a disabled tracer costs one attribute check and no
        event-arg construction."""
        t = self.tracer
        return t if (t is not None and t.enabled) else None

    def _co(self):
        """The active cost observatory, or None — THE guard every cost
        site uses (``_tr()``'s twin), so a disabled/absent observatory
        costs one attribute check and no accounting work. Also the
        chokepoint that keeps the prefix cache's tier ledger pointed at
        the live observatory: the gateway installs ``engine.cost``
        AFTER construction (and swaps it on rebuild), and the trie's
        spill/readmit paths record through ``prefix_cache.cost`` — one
        identity check per step keeps the two in sync."""
        c = self.cost
        co = c if (c is not None and c.enabled) else None
        pc = self.prefix_cache
        if pc is not None and pc.cost is not co:
            pc.cost = co
        return co

    def _wrap_prog(self, key, fn, host_out):
        """The jit-cache hand-out chokepoint: every program accessor
        routes through here, so with the observatory on, EVERY device
        program the engine can launch is counted — exactly once per
        call, no site-by-site bookkeeping to drift. ``host_out`` names
        the result indices the engine fetches to host (the program's
        true device→host surface)."""
        co = self._co()
        if co is None:
            return fn
        return co.wrap(key, fn, host_out=host_out)

    #: the cost observatory's name for the phase a mark enters, where it
    #: names one: its ``launch`` goes on covering ``device-wait``, and
    #: ``other`` keeps the name the step had
    _COST_PHASE = {"admit": "admit", "plan": "plan", "dispatch": "launch",
                   "host-accept": "host-accept"}

    def _mark(self, phase, span=False, args=None, end=None, end_args=None,
              outer=None, outer_args=None):
        """A boundary between two phases of the thread driving
        :meth:`step`, written once and read three ways. It enters
        ``phase`` on the driver clock (``profiler.driver_clock.PHASES``;
        always on under a gateway), names the cost observatory's phase
        (:attr:`_COST_PHASE`), and when the tracer records closes the span
        ``end`` (then ``outer``, the ``launch`` around it) and, with
        ``span=True``, opens the span named ``phase``, all at the mark's
        one wall reading, so that a phase's spans sum to what the clock
        charged it. Returns the span opened, or None with tracing off:
        sites build span args behind it (``end_args=sp and {...}``), so
        the disabled path allocates nothing. A step passes through
        ``sweep`` (its start: deadlines, the fault hook, policy, the
        scheduler's admissions), ``admit``, ``plan``, ``dispatch``,
        ``device-wait``, ``host-accept`` and ``retire`` (step accounting,
        ``on_step``, a traced step's counter samples, the return);
        ``other`` is what lies between them: ten phases with the
        gateway's ``loop`` and ``idle-wait``."""
        pc = self.driver_clock
        t = pc.enter(phase) if pc is not None else None
        name = self._COST_PHASE.get(phase)
        if name is not None:
            co = self._co()
            if co is not None:
                co.set_phase(name)
        if end is not None:
            end.end(end_args, t1=t)
        if outer is not None:
            outer.end(outer_args, t1=t)
        if span:
            tr = self._tr()
            if tr is not None:
                return tr.span(phase, args=args, t0=t)
        return None

    def _mark_plan(self):
        """Enter ``plan``: the first mark of each of the three steps, which
        ends the step's ``sweep`` where no admission did."""
        sweep, self._sweep = self._sweep, None
        return self._mark("plan", span=True, end=sweep,
                          end_args=sweep and {"admitted": 0})

    def _mark_retire(self, end=None, end_args=None, outer=None,
                     outer_args=None):
        """Enter ``retire``, the last phase of a step: its span stays open
        until :meth:`step` closes it with the ``step`` span, at one
        reading; the clock's phase runs on to the driver's next mark."""
        self._retire_span = self._mark(
            "retire", span=True, end=end, end_args=end_args, outer=outer,
            outer_args=outer_args)

    def _stamp_now(self):
        """Timestamp for the Sequence SLO stamps: the current step's
        start reading while inside a step (no extra clock reads — see
        ``_stamp_t``), a fresh reading outside one (submit/cancel)."""
        return self._stamp_t if self._stamp_t is not None \
            else self._clock()

    def _trace_phase_end(self, tr, seq, args=None):
        """Close the sequence's current lifecycle span (named by its
        ``trace_phase``: queued|prefill|decode|preempted|recovered)
        on the request's trace lane and restart the mark."""
        tr.complete(seq.trace_phase, seq.trace_mark,
                    tid=tr.req_tid(seq.request_id), args=args)
        seq.trace_mark = tr.now()

    def _tspan(self, name, args=None):
        """Engine-lane span context manager, or a shared no-op when
        tracing is off. Convenience for the prefill paths; the
        per-step hot sites use explicit ``_tr()`` guards so the
        disabled path never builds an args dict."""
        tr = self._tr()
        if tr is None:
            return NULL_SPAN
        return tr.span(name, args=args)

    def _dispatch_args(self, qstart, qlen, kvlen, packed, decode_rows,
                       decode_tokens, prefill_tokens):
        """The ``dispatch`` span's args: what this step asks of the ragged
        kernel in one layer call (every layer runs the same grid;
        ``kernels.pallas_ragged_attention.ragged_grid_counts``) and the
        step's tokens by kind, counted at the tiling the step's kernel
        derives for itself (``decode.attention_grid``)."""
        heads = self.config.num_attention_heads // self._tp
        # (a model whose kernel head is a PAIR of heads says so)
        head_dim = getattr(self.config, "kernel_head_dim",
                           self.config.head_dim)

        pool = self.cache.pool

        def counts(qstart, qlen, packed, k=pool.k, v=pool.v, **window):
            # (KV heads as the dense kernel sees them in the store's row,
            # the pool's or a ring's of its own; the latent kernel has none,
            # nor the walk ``span_row_groups`` counts)
            kv_heads = None if "wkv_a" in self._params else \
                k.shape[-1] // self._tp // head_dim
            return ragged_grid_counts(
                qstart, qlen, kvlen, packed_tokens=packed,
                heads=heads, block_size=self.cache.block_size,
                table_entries=self.cache.max_blocks, kv_heads=kv_heads,
                **window,
                **attention_grid(self._params, k, self.cache.max_blocks,
                                 heads, packed, tp=self._tp,
                                 head_dim=head_dim, pool_v=v))

        work = counts(qstart, qlen, packed)
        work.update(decode_rows=decode_rows, decode_tokens=decode_tokens,
                    prefill_tokens=prefill_tokens)
        if "window_layers" in self._params:
            # the kernel's calls by layer kind: ``work`` is a full layer's,
            # over the pool; a window layer's call walks its ring, whose row
            # is its own (other KV heads: another tiling), and needs the
            # keys inside the window only; what it FETCHES is whole blocks
            # of whole groups from the group its window starts in
            win = counts(qstart, qlen, packed, *self.cache.window,
                         window=self.config.sliding_window)
            work.update(
                window_kv_tokens=win["kv_tokens"],
                window_attn_pairs=win["attn_pairs"],
                window_fetched_keys=win["live_steps"] * self.cache.block_size,
                window_update_steps=win["update_steps"])
        elif self.cache.window is not None:
            # the kernel's calls by layer kind: ``work`` is the middle full
            # layer's; a window layer's call needs the keys inside the
            # window only (``window_kv_tokens``); a cross layer's runs one
            # row a slot (the buffer narrows to ``cross_rows`` after the
            # middle layers) over the same cache, so its ``kv_tokens`` are
            # the middle layer's
            work["window_kv_tokens"] = counts(
                qstart, qlen, packed,
                window=self.config.sliding_window)["kv_tokens"]
            work["cross_rows"] = self.num_slots
        if "idx_layer" in self._params:
            # attention over a selection, summed over the step's layers:
            # the queries an indexer scored and the keys it scored them
            # against, the sets' sizes, and the pool rows the attention
            # scored (a masked walk's whole diagonal; a gather would read
            # the sets themselves); and the spans of one token that the
            # index-scores kernel scores on their own wide rows, at the
            # tiling its call derives (0 where that has no such path)
            d, layers = self.config.dsa, self.config.num_hidden_layers
            spans = [(int(ql), int(kl)) for ql, kl in zip(qlen, kvlen)
                     if ql > 0]
            picked = sum(int(np.minimum(np.arange(kl - ql, kl) + 1,
                                        d.topk).sum()) for ql, kl in spans)
            alone = index_grid_params(d.heads, packed)["one_token"] \
                * sum(ql == 1 for ql, _ in spans)
            work.update(
                index_query_rows=d.layers * sum(ql for ql, _ in spans),
                index_one_token_rows=d.layers * alone,
                index_key_rows=d.layers * work["attn_pairs"],
                selected_rows=layers * picked,
                attended_rows=layers * work["attn_pairs"])
        if self._stateful:
            # what ONE linear layer call does: the rows whose state it
            # reads and writes (every live span's slot), and what it sends
            # through the chunked scan (the spans longer than one token)
            spans = [int(n) for n in qlen if n > 1]
            work.update(state_rows=int((np.asarray(qlen) > 0).sum()),
                        scan_tokens=sum(spans), scan_spans=len(spans))
        if "ssd_layers" in self._params:
            # the Mamba-2 kernels' work summed over the step's blocks: the
            # rows of one token through ``ssd_recurrent_update``, the longer
            # spans' tokens through ``ssd_chunk_scan``
            units = self.config.num_units
            work.update(
                ssd_update_rows=units * int((np.asarray(qlen) == 1).sum()),
                ssd_scan_tokens=units * work["scan_tokens"],
                ssd_scan_spans=units * work["scan_spans"])
        return work

    # ------------------------------------------------------------ programs
    def _fn_consts(self):
        c = self.config
        consts = dict(nh=c.num_attention_heads, nkv=c.num_key_value_heads,
                      hd=c.head_dim, eps=float(c.rms_norm_eps),
                      theta=None if c.rope_theta is None
                      else float(c.rope_theta), tied=self._tied)
        if getattr(c, "rotary_dim", None):
            # the first values of a head that the rotary embedding turns,
            # where that is not the whole head
            consts["rotary"] = int(c.rotary_dim)
        if self.routed_ffn:
            # the routed FFN's static numbers, model hyper-parameters like
            # the head counts above
            consts["moe"] = getattr(c, "routing", None) or (
                int(c.num_experts_per_tok), bool(c.norm_topk_prob))
        if "wkv_a" in self._params:
            consts["mla"] = c.mla
        if "idx_layer" in self._params:
            consts["dsa"] = c.dsa
        # a store's kernels leave the chunk scan out of the decode-only
        # program, whose spans are one token each (where there is one)
        rows = self._decode_rows if len(self._step_rows) == 2 else 0
        if "linear_layers" in self._params:
            consts["gdn"] = c.gdn._replace(decode_rows=rows)
        elif "ssd_layers" in self._params:
            consts["ssd"] = c.ssd._replace(decode_rows=rows)
        elif "window_layers" in self._params:
            consts["swa"] = c.swa._replace(
                ring_rows=self._ring_blocks * self.cache.block_size)
        elif self._stateful:
            consts["ssm"] = c.ssm._replace(
                ring_rows=self._ring_blocks * self.cache.block_size,
                decode_rows=rows)
        if self._routing is not None:
            consts["return_picks"] = True
        return consts

    def _tp_consts(self):
        """Builder kwargs of the TP variant ({} on tp=1, so default
        engines call the builders exactly as before)."""
        if self._tp <= 1:
            return {}
        return dict(tp=self._tp, collective_dtype=self._coll_dtype,
                    kv_quant=self._kv_quant, wq8=self._wq8)

    def _q_consts(self):
        """Builder kwargs of the activation-quantized variant ({} when
        off, so default engines call the builders exactly as before).
        Only the builders that grew the int8xint8 path take ``a8``."""
        return dict(a8=True) if self._a8 else {}

    def _moe_out(self, index):
        """The result index of a program's routing summary, as a tuple:
        empty for a model with a dense FFN, whose programs return none."""
        return (index,) if self.routed_ffn else ()

    def _count_moe(self, summary):
        """Add one program call's routing summary (``()`` from a dense
        model's program, else one ``[L, 5]`` int32 array, per routed layer
        ``kernels.moe_ffn.STATS``: live pairs on held experts, experts
        touched, the fullest expert's pairs, picks made, 1 for a call of
        one pass on the buffer of the pairs this chip's experts take) to the
        always-on counters. The array rides the fetch that fences the
        step's tokens: no second sync. Returns the call's totals as span
        args, or None."""
        if not summary:
            return None
        st = np.asarray(summary[0])
        call = {"moe_" + name: int(st[:, i].sum())
                for i, name in enumerate(MOE_STATS)}
        call["moe_layer_calls"] = int(st.shape[0])
        for k, v in call.items():
            self.stats[k] += v
        return call

    def _prefill_fn(self):
        # the weight tag (not the kv tag): the cold prefill touches the
        # params but never the pool, so two engines differing only in
        # kv_dtype SHARE this trace while a quantized-weights engine
        # (different param pytree = different trace) keys apart. The
        # TP tag joins: a sharded prefill is a different program.
        key = ("prefill",) + self._wtag + self._atag + self._tptag
        if key not in self._jit:
            tpk = self._tp_consts()
            tpk.pop("kv_quant", None)   # prefill never touches the pool
            self._jit[key] = build_prefill_fn(**self._fn_consts(), **tpk,
                                              **self._q_consts())
        # host_out: the engine fetches tok0 (result 2) and, of a
        # routed-FFN model, the routing summary (result 4); pk/pv feed
        # the cache writer device-side and keys stay device state
        return self._wrap_prog(key, self._jit[key],
                               host_out=(2,) + self._moe_out(4))

    def _suffix_fn(self):
        # the suffix program touches params AND pool — all three tags
        key = ("psuffix",) + self._kvtag + self._wtag + self._atag \
            + self._tptag
        if key not in self._jit:
            self._jit[key] = build_paged_suffix_prefill_fn(
                **self._fn_consts(), **self._tp_consts(),
                **self._q_consts())
        return self._wrap_prog(key, self._jit[key], host_out=(2,))

    def _ragged_fn(self, n_steps, rows):
        # the full packed-buffer geometry — num_slots AND token budget,
        # not their sum alone — is part of the key: engines with
        # different geometry sharing one jit_cache must not pool their
        # shape-keyed traces under one fn (decode_compilations counts
        # only THIS engine's geometry, and e.g. slots=8/chunk=64 vs
        # slots=16/chunk=56 share a token budget of 72). ``rows``, the
        # packed size this step runs at (one of the engine's two), joins
        # it: one body, one program a size, each counted and costed
        # (``_wrap_prog``) under its own name
        key = ("ragged", self.num_slots, self._token_budget, int(rows),
               int(n_steps), self.config.decode_attention) \
            + self._kvtag + self._wtag + self._atag + self._tptag
        if key not in self._jit:
            self._jit[key] = build_ragged_step_fn(
                n_steps=int(n_steps),
                decode_attn=self.config.decode_attention,
                collective_overlap=self._coll_overlap,
                **self._fn_consts(), **self._tp_consts(),
                **self._q_consts())
        # host reads the sampled tokens and a routed-FFN model's routing
        # summary (result 5); tok_fin and the key state stay on the
        # device and feed the next step's program
        return self._wrap_prog(key, self._jit[key],
                               host_out=(2,) + self._moe_out(5))

    def _mtick_fn(self):
        # like the ragged key: the full packed geometry (num_slots AND
        # token budget) plus max_ticks — CONFIG, like the spec key's
        # spec_len — key the trace apart from other engines sharing
        # one jit_cache. The tick count actually run is a runtime
        # argument, so this is the engine's ONE decode program.
        key = ("mtick", self.num_slots, self._token_budget,
               self._decode_ticks, self.config.decode_attention) \
            + self._kvtag + self._wtag + self._atag + self._tptag
        if key not in self._jit:
            from .decode import build_multitick_step_fn
            self._jit[key] = build_multitick_step_fn(
                max_ticks=self._decode_ticks,
                decode_attn=self.config.decode_attention,
                collective_overlap=self._coll_overlap,
                **self._fn_consts(), **self._tp_consts(),
                **self._q_consts())
        # host reads the sampled token block, the key walk (per-slot
        # adoption at each slot's trim cut) and the ticks-run scalar
        return self._wrap_prog(key, self._jit[key], host_out=(2, 3, 4))

    def _spec_fn(self):
        # like the ragged key: the full packed geometry (num_slots AND
        # the spec token budget) plus the sampling-walk depth key the
        # trace apart from other engines sharing one jit_cache
        key = ("spec", self.num_slots, self._spec_budget,
               self._spec_len, self.config.decode_attention) \
            + self._kvtag + self._wtag + self._atag + self._tptag
        if key not in self._jit:
            from .decode import build_spec_verify_fn
            self._jit[key] = build_spec_verify_fn(
                spec_len=self._spec_len,
                decode_attn=self.config.decode_attention,
                collective_overlap=self._coll_overlap,
                **self._fn_consts(), **self._tp_consts(),
                **self._q_consts())
        # host reads the sampled walk tokens AND the key walk (both are
        # np.asarray'd for acceptance)
        return self._wrap_prog(key, self._jit[key], host_out=(2, 3))

    @property
    def routed_ffn(self) -> bool:
        """Whether the model's FFN is a routed (mixture-of-experts) one,
        whose step programs return a routing summary — the public surface
        for banners/metrics."""
        return "router" in self._params

    @property
    def spec_decode(self) -> bool:
        """Whether this engine runs speculative multi-token decode
        (draft → ragged-span verify → block-tail rollback) — the public
        surface for banners/metrics."""
        return self._spec

    @property
    def spec_k(self) -> int:
        """Max draft tokens per verify span (0 when speculation is
        off)."""
        return self._spec_k if self._spec else 0

    @property
    def decode_ticks(self) -> int:
        """Max on-device decode ticks per host sync (1 = the unified
        single-sync-per-token step) — the public surface for
        banners/metrics. README "Multi-tick decode"."""
        return self._decode_ticks

    @property
    def tp(self) -> int:
        """Tensor-parallel degree: the number of mesh devices every
        serving program shards over (1 = single-chip, no mesh) — the
        public surface for banners/metrics (README "Tensor-parallel
        serving")."""
        return self._tp

    @property
    def collective_dtype(self) -> str:
        """The EFFECTIVE wire dtype of the per-layer TP all-reduce:
        ``"int8"`` runs it EQuARX-style block-quantized, ``"fp"`` is a
        plain psum (and the reported value on tp=1, where no collective
        ever runs) — the public surface for banners/metrics."""
        return self._coll_dtype

    @property
    def collective_overlap(self) -> bool:
        """Whether the per-layer TP all-reduce pair runs the chunked
        reduce-scatter/all-gather overlap schedule instead of one psum
        (False on tp=1, where no collective ever runs) — the public
        surface for banners/metrics (README "Collective overlap")."""
        return self._coll_overlap

    def _record_collectives(self, co, spans):
        """EXACT collective-byte accounting for one sharded launch —
        called at every launch site behind the ``_co()`` guard.
        ``spans`` is ``[(rows, repeats)]``: each entry covers
        ``repeats`` passes over the layer stack, each pass paying the
        per-layer all-reduce PAIR (post o-proj + post down-proj) on a
        ``[rows, hidden]`` activation. Bytes follow the shared wire
        model (``quantization.collective_wire_bytes``), so the
        fp-vs-int8 counter ratio is shape-derived and deterministic —
        the TP bench's >=3x gate reads these counters, not a network
        probe."""
        if self._tp <= 1:
            return
        from ..quantization import collective_wire_bytes
        L = self.config.num_hidden_layers
        hidden = self.config.hidden_size
        fp_b = np.dtype(self._params["embed"].dtype).itemsize
        ops, nbytes = 0, 0
        for rows, reps in spans:
            if rows <= 0 or reps <= 0:
                continue
            ops += 2 * L * reps
            nbytes += 2 * L * reps * collective_wire_bytes(
                rows, hidden, self._tp, self._coll_dtype,
                fp_itemsize=fp_b)
        co.record_collective(self._coll_dtype, ops, nbytes)

    @property
    def kv_dtype(self) -> str:
        """The EFFECTIVE KV storage dtype this engine serves from:
        ``"int8"`` / ``"fp8"`` on a quantized pool, else the pool's
        array dtype name — the public surface for banners/metrics
        (README "Quantized serving")."""
        if self._kv_quant:
            return self._kv_dtype
        return str(self.cache.pool.k.dtype)

    @property
    def quantize_weights(self) -> bool:
        """Whether the decode-path projection matmuls run int8
        weight-only (converted once at engine build) — the public
        surface for banners/metrics."""
        return self._wq8

    @property
    def quantize_activations(self) -> bool:
        """Whether the decode-path projections run int8xint8 — per-row
        runtime activation quant contracted against the int8 weights
        with int32 accumulate, no per-layer weight dequant — the public
        surface for banners/metrics (README "Quantized serving")."""
        return self._a8

    @property
    def prefill_chunk(self) -> int:
        """The EFFECTIVE chunked-prefill budget this engine runs: the
        configured value rounded up to a KV-block multiple, or 0 when
        chunking is disabled. The public surface for
        banners/metrics; ``_chunk`` stays the internal None-able
        form."""
        return self._chunk or 0

    @property
    def step_rows(self) -> tuple:
        """The packed sizes this engine's step programs can have,
        ascending: the unified step's decode-only size (``num_slots``
        rounded up to 8) and, where a prompt can be chunked, its token
        budget ``num_slots + prefill_chunk``; the multi-tick and the
        speculative step have one. The label values of
        ``serving_step_programs_total{rows}``."""
        return self._step_rows

    def decode_compilations(self) -> int:
        """Total decode-program traces OF THIS ENGINE'S KIND (the
        compiles-once assertion hook): stays at one per ``(packed size
        reached, n_steps)`` — at most two sizes (:attr:`step_rows`), a
        size counted from the first step that ran it — no matter how
        request sampling params /
        token budgets / block tables / span mixes vary. Engines of
        another geometry or variant sharing one jit_cache count only
        their own programs. On the speculative
        engine the verify program IS the decode program — every step,
        chunk-carrying or not, is one spec-geometry launch — so the
        count covers the verify geometry too. Tag-aware INCLUSIVE of
        the sharded geometry: a tp=N engine counts only its own
        ``("tpN", dtype)``-tagged traces, so the pin covers the
        shard_map program and a tp=1 sibling sharing the jit cache
        never pollutes it (README "Tensor-parallel serving")."""
        tags = self._kvtag + self._wtag + self._atag + self._tptag
        if self._spec:
            # spec_len is CONFIG (spec_k + 1), not a runtime variant
            # like the ragged key's n_steps — two engines differing
            # only in spec_k can share a budget (the chunk term of the
            # max dominates), so it must be part of the identity.
            # key[5:] is the quantization-variant tail: a quantized
            # engine sharing this jit_cache is a different program.
            return sum(fn._cache_size() for key, fn in self._jit.items()
                       if key[0] == "spec"
                       and key[1] == self.num_slots
                       and key[2] == self._spec_budget
                       and key[3] == self._spec_len
                       and key[5:] == tags)
        if self._mtick:
            # the multi-tick program IS the decode program — every
            # step, chunk-carrying or not, is one mtick-geometry launch
            # whose tick count is a runtime argument, so the count
            # covers the multi-tick geometry with a single trace.
            # decode_ticks is CONFIG (part of the identity, like the
            # spec key's spec_len): two engines differing only in
            # decode_ticks share a packed budget but not a program.
            return sum(fn._cache_size() for key, fn in self._jit.items()
                       if key[0] == "mtick"
                       and key[1] == self.num_slots
                       and key[2] == self._token_budget
                       and key[3] == self._decode_ticks
                       and key[5:] == tags)
        return sum(fn._cache_size() for key, fn in self._jit.items()
                   if key[0] == "ragged"
                   and key[1] == self.num_slots
                   and key[2] == self._token_budget
                   and key[6:] == tags)

    def prefill_compilations(self) -> int:
        """Prefill-side traces, cold + suffix: bounded by the pow2
        (group, bucket) grid — independent of the hit/miss/eviction mix
        (the bounded-compile half of the prefix-cache contract). Tag-
        aware like :meth:`decode_compilations`: only THIS engine's
        quantization variant counts."""
        return sum(fn._cache_size() for key, fn in self._jit.items()
                   if (key[0] == "prefill"
                       and key[1:] == self._wtag + self._atag
                       + self._tptag)
                   or (key[0] == "psuffix"
                       and key[1:] == self._kvtag + self._wtag
                       + self._atag + self._tptag))

    # ------------------------------------------------------------- intake
    def _key_for(self, request):
        if request.prng_key is not None:
            return jnp.asarray(request.prng_key)
        if request.seed is not None:
            return jax.random.PRNGKey(int(request.seed))
        from ..core import random as random_mod
        return random_mod.next_key()

    def validate(self, request):
        """Raise the submit-time errors without mutating engine state —
        callable from any thread (the HTTP front door pre-validates here
        so a bad request 400s on the handler thread instead of poisoning
        the driver loop)."""
        if not isinstance(request, GenerationRequest):
            raise TypeError(
                f"submit() takes a GenerationRequest, got "
                f"{type(request).__name__}")
        prompt_len = int(np.asarray(request.prompt).reshape(-1).shape[0])
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if int(request.max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens}")
        if prompt_len + int(request.max_new_tokens) > self.max_seq_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds the KV cache length "
                f"({self.max_seq_len}); raise max_seq_len or generate "
                f"fewer tokens")
        if request.timeout_s is not None and float(request.timeout_s) <= 0:
            raise ValueError(
                f"timeout_s must be > 0, got {request.timeout_s}")
        # unknown priority_class raises here — on the caller's thread,
        # so the HTTP front door 400s instead of poisoning the driver
        self.classes.resolve(request.priority_class)

    def submit(self, request) -> Sequence:
        """Queue a request; returns its live Sequence handle."""
        self.validate(request)
        deadline = (time.monotonic() + float(request.timeout_s)
                    if request.timeout_s is not None else None)
        seq = Sequence(request, key=self._key_for(request),
                       submit_step=self.stats["steps"], deadline=deadline)
        seq.pclass = self.classes.resolve(request.priority_class)
        seq.t_submit = self._clock()
        tr = self._tr()
        if tr is not None:
            seq.trace_mark = tr.now()
        self.scheduler.submit(seq)
        return seq

    def cancel(self, seq: Sequence) -> bool:
        """Retire a sequence with ``finish_reason="cancelled"`` — queued
        (dropped before ever touching a slot), mid-chunked-prefill (the
        partial block chain is freed, or donated when a trie is on), or
        running (KV slot freed mid-decode; the ragged kernel skips the
        dead slot from the next step on). Must be called from the
        thread driving :meth:`step`. Returns False if the sequence
        already finished."""
        if seq.done:
            return False
        if seq.status == "queued":
            if not self.scheduler.remove(seq):
                return False
        else:
            self._drain("cancel")
            if seq.done:        # the token in flight was its last
                return False
        self.stats["cancelled"] += 1
        self._finish(seq, "cancelled", [])
        return True

    # ------------------------------------------------------------ stepping
    def _admission_hit_len(self, seq):
        """THE prefix lookup for one admitted sequence (the scheduler
        calls this once per pop): records hit/miss stats, pins the
        matched chain immediately — before any admission this step can
        publish-and-evict — and stores it on the sequence for
        _admit_group to install. Returns the covered token count."""
        matched = self.prefix_cache.lookup(seq.work)
        if matched:
            self.prefix_cache.acquire(matched)
            seq.prefix_nodes = matched
        return self.prefix_cache.block_size * len(matched)

    def _bucket(self, plen):
        if self._bucketing == "exact":
            return plen
        return min(max(8, 1 << (plen - 1).bit_length()), self.max_seq_len)

    def _admit_group(self, seqs, finished):
        """Admit a batch of sequences. With the prefix cache enabled the
        batch splits on cached-chain lookup: misses take the cold path
        (ONE full-prompt prefill device call per prompt-length bucket, or
        several of at most ``WHOLE_PROMPT_ROWS`` rows where a burst is larger),
        hits install their cached blocks and take the suffix path (ONE
        suffix prefill per suffix-length bucket). Both pad the group dim
        to a power of two, so compile count stays bounded at
        O(log(num_slots) × buckets) regardless of the hit mix.

        With chunked prefill on, a sequence whose UNCOVERED prompt
        exceeds ``prefill_chunk`` skips both one-shot paths: it claims
        its slot (and zero-copy-installs any matched chain) now, enters
        the PREFILLING state, and the step loop feeds it to the step
        program one budgeted chunk at a time."""
        tr = self._tr()
        for seq in seqs:
            if tr is not None:
                # close each admitted sequence's waiting span (named by
                # the phase that just ended: queued, or preempted/
                # recovered for a readmission) on its request lane
                self._trace_phase_end(
                    tr, seq,
                    args={"prefix_hit_tokens": seq.prefix_hit_tokens})
            # the phase NAME tracks state even with tracing off (one
            # attr store): a capture window opened mid-flight must
            # close this request's next span under the right name
            seq.trace_phase = "prefill"
        cold, hits = [], []
        for seq in seqs:
            # the lookup already ran (and pinned) in _admission_hit_len
            # at scheduler pop time — before ANY admission this step: a
            # cold sequence retiring instantly (max_new_tokens=1 /
            # immediate EOS) publishes inside _admit_cold, and under
            # pool pressure that publish evicts; an unpinned matched
            # chain could be reaped and its block re-used before
            # _admit_hits installs it
            covered = seq.prefix_hit_tokens   # set at scheduler pop time
            if self._chunk and seq.work_len - covered > self._chunk:
                self._enter_chunked_prefill(seq, covered)
            elif seq.prefix_nodes:
                hits.append((seq, seq.prefix_nodes))
            else:
                cold.append(seq)
        if cold:
            self._admit_cold(cold, finished)
        if hits:
            self._admit_hits(hits, finished)

    def _enter_chunked_prefill(self, seq, covered):
        """Claim a slot for a long prompt without prefilling it yet: an
        installed prefix-cache hit counts toward the resume offset
        (zero-copy table references, exactly as on the one-shot hit
        path); everything past it arrives chunk by chunk."""
        slot = self.cache.alloc()
        seq.slot = slot
        if seq.prefix_nodes:
            self.cache.install_prefix(
                slot, [node.block_id for node in seq.prefix_nodes])
        seq.prefilled = covered
        self.cache.lengths[slot] = covered
        # the chunk rows carry the key as a host array: fetch it once,
        # here, where admission may wait for the chip, and never in a plan
        seq.key = np.asarray(seq.key, np.uint32)
        seq.status = "prefilling"
        if seq.t_admitted is None:      # first claim only: queue wait
            seq.t_admitted = self._stamp_now()  # kept across restore
        self._slots[slot] = seq
        self.scheduler.enter_prefill(seq)

    def _admit_cold(self, seqs, finished):
        by_bucket = {}
        for seq in seqs:
            by_bucket.setdefault(self._bucket(seq.work_len), []).append(seq)
        # a bucket's sequences in calls of at most WHOLE_PROMPT_ROWS rows
        # (a power of two of them, as the padded group is)
        calls = []
        for s_pad, group in sorted(by_bucket.items()):
            most = 1 << (max(WHOLE_PROMPT_ROWS // s_pad, 1).bit_length() - 1)
            calls += [(s_pad, group[i:i + most])
                      for i in range(0, len(group), most)]
        for s_pad, group in calls:
            G = len(group)
            Gp = 1 << (G - 1).bit_length()
            ids = np.zeros((Gp, s_pad), np.int32)
            lens = np.ones(Gp, np.int32)  # pad rows: 1 valid token
            temps = np.zeros(Gp, np.float32)
            topks = np.zeros(Gp, np.int32)
            keys = np.zeros((Gp, 2), np.uint32)
            for i, seq in enumerate(group):
                ids[i, :seq.work_len] = seq.work
                lens[i] = seq.work_len
                temps[i] = float(seq.request.temperature)
                topks[i] = int(seq.request.top_k)
                keys[i] = np.asarray(seq.key)
            with self._tspan("prefill_launch",
                             args={"bucket": s_pad, "group": G}) as sp:
                # host arrays pass uncoerced: jit device_puts them
                # identically, and the cost facade then counts the
                # REAL host→device upload bytes of the call
                pk, pv, tok0s, keys2, *moe = self._prefill_fn()(
                    self._params, ids, lens, keys, temps, topks)
                # a model with recurrent layers: last, what their cache
                # holds of each row (states, convolution tails)
                state = moe.pop() if self._stateful else None
                tok0s = np.asarray(tok0s)
                sp.add(self._count_moe(moe))
            if self._routing is not None:
                self._routing.note(moe[1], [
                    (seq, i * s_pad, seq.work_len, 0)
                    for i, seq in enumerate(group)])
            co = self._co()
            if co is not None:
                # sharded cold prefill: one pass over the padded group
                self._record_collectives(co, [(Gp * s_pad, 1)])
            for i, seq in enumerate(group):
                seq.launches += 1       # rode this bucket's prefill
                slot = self.cache.alloc()
                seq.slot = slot   # before the write: a PoolExhausted
                # raised inside write_prefill's block growth must leave
                # the claimed slot findable for _abort_admission
                self.cache.write_prefill(slot, pk[:, i], pv[:, i],
                                         seq.work_len)
                if state is not None:
                    self.cache.write_state(slot, *(a[:, i] for a in state))
                    self.stats["state_rows"] += 1
                self._install_seq(seq, slot, tok0s[i], keys2[i],
                                  seq.work_len, finished)

    def _admit_hits(self, hits, finished):
        """Admit prefix-cache hits, then ONE suffix-prefill device call
        per suffix-length bucket covering only the uncovered prompt
        tails.

        ZERO-COPY install — the slot's block table simply references
        the matched chain's block ids (no device dispatch; N concurrent
        holders share the physical blocks), private tail blocks are
        appended to cover the prompt, and the suffix prefill writes
        through the table. Group padding rows carry all-sentinel tables
        and an all-covered prefix, so every one of their writes drops
        in-program."""
        bs, mb = self.cache.block_size, self.cache.max_blocks
        by_bucket = {}
        for seq, matched in hits:
            suffix_len = seq.work_len - len(matched) * bs
            by_bucket.setdefault(self._bucket(suffix_len),
                                 []).append((seq, matched))
        for s_pad, group in sorted(by_bucket.items()):
            Gp = 1 << (len(group) - 1).bit_length()
            tables = np.full((Gp, mb), self.cache.sentinel, np.int32)
            prefix_lens = np.full(Gp, mb * bs, np.int32)
            ids = np.zeros((Gp, s_pad), np.int32)
            suf_lens = np.ones(Gp, np.int32)
            temps = np.zeros(Gp, np.float32)
            topks = np.zeros(Gp, np.int32)
            keys = np.zeros((Gp, 2), np.uint32)
            for i, (seq, matched) in enumerate(group):
                # chain already pinned + prefix_hit_tokens already set
                # by _admission_hit_len at scheduler pop time
                covered = len(matched) * bs
                slot = self.cache.alloc()
                seq.slot = slot
                self.cache.install_prefix(
                    slot, [node.block_id for node in matched])
                self.cache.ensure_capacity(slot, seq.work_len)
                tables[i] = self.cache.tables[slot]
                ids[i, :seq.work_len - covered] = seq.work[covered:]
                suf_lens[i] = seq.work_len - covered
                prefix_lens[i] = covered
                keys[i] = np.asarray(seq.key)
                temps[i] = float(seq.request.temperature)
                topks[i] = int(seq.request.top_k)
            with self._tspan("prefill_launch",
                             args={"bucket": s_pad, "group": len(group)}):
                # host arrays pass uncoerced (see _admit_cold): the cost
                # facade counts the call's real host→device upload bytes
                nk, nv, tok0s, keys2 = self._suffix_fn()(
                    self._params, *self.cache.kv_args(), tables,
                    prefix_lens, ids, suf_lens, keys, temps, topks)
                self.cache.update(nk, nv)
                tok0s = np.asarray(tok0s)
            co = self._co()
            if co is not None:
                # sharded suffix prefill: one pass, padded group
                self._record_collectives(co, [(Gp * s_pad, 1)])
            for i, (seq, matched) in enumerate(group):
                seq.launches += 1       # rode this bucket's suffix call
                slot = seq.slot
                self.cache.lengths[slot] = seq.work_len
                self.stats["prefill_tokens_saved"] += seq.prefix_hit_tokens
                self._install_seq(seq, slot, tok0s[i], keys2[i],
                                  seq.work_len - seq.prefix_hit_tokens,
                                  finished)

    def _advance_chunk(self, seq, n, tok0, key0, finished, offset=None):
        """Per-chunk completion bookkeeping shared by the three step
        variants — the ONE place chunk accounting and the final-chunk
        install live, so they cannot silently diverge.
        ``tok0``/``key0`` are the chunk
        row's sampled token + advanced key, consumed only when this
        chunk completes the prompt. The unified step advanced
        ``seq.prefilled`` when it dispatched the chunk and passes the
        chunk's own ``offset``; its program installed the key itself
        (``key0`` None)."""
        off = seq.prefilled if offset is None else offset
        slot, end = seq.slot, off + n
        seq.launches += 1               # rode this chunk's device call
        self.stats["prefill_chunks"] += 1
        self.stats["chunk_tokens"] += n
        tr = self._tr()
        if tr is not None:
            # one lifecycle span per chunk on the request's lane:
            # prefill_chunk[i] from the previous mark (admission or the
            # prior chunk) to this chunk's host completion
            tr.complete(f"prefill_chunk[{seq.trace_chunk_i}]",
                        seq.trace_mark, tid=tr.req_tid(seq.request_id),
                        args={"tokens": n, "offset": off})
            seq.trace_mark = tr.now()
            seq.trace_chunk_i += 1
        self.cache.lengths[slot] = end
        if offset is None:
            seq.prefilled = end
        if end == seq.work_len:             # work content complete
            self.scheduler.leave_prefill(seq)
            self.stats["prefill_tokens_saved"] += seq.prefix_hit_tokens
            self._install_seq(seq, slot, tok0, key0,
                              seq.work_len - seq.prefix_hit_tokens,
                              finished)

    def _install_seq(self, seq, slot, tok0, key2, prefilled_tokens,
                     finished):
        """Post-prefill slot bookkeeping shared by the cold and hit
        admission paths — the ONE place a future per-slot knob gets
        wired, so the two paths cannot silently diverge.
        ``prefilled_tokens`` is the device prefill work actually done
        (full prompt cold, uncovered suffix on a hit).

        A RESTORED sequence (``restore_point > 0``, recovery-by-
        recompute after a crash or preemption) takes the same slot
        bookkeeping but adopts no sampled output: its next decode input
        is the last token it already streamed (for a greedy request the
        prefill's argmax reproduces it anyway — the logits at the end of
        ``work`` are the logits that sampled it originally) and its PRNG
        walk resumes from the key snapshot taken when it was displaced,
        so the continuation is byte-identical and no consumer ever sees
        a replayed token."""
        seq.slot = slot
        seq.status = "running"
        if seq.t_admitted is None:      # first claim only: queue wait
            seq.t_admitted = self._stamp_now()  # kept across restore
        tr = self._tr()
        if tr is not None:
            self._trace_phase_end(
                tr, seq, args={"prefix_hit_tokens": seq.prefix_hit_tokens,
                               "restored": bool(seq.restore_point)})
        seq.trace_phase = "decode"      # tracked even with tracing off
        self._slots[slot] = seq
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += int(prefilled_tokens)
        # key2 None: the unified step's program merged the slot's key into
        # the device key state itself (``chunk_keys`` / ``adopt``)
        if seq.restore_point:
            self._last_tok[slot] = int(seq.tokens[-1])
            if key2 is not None:
                self._keys = self._keys.at[slot].set(jnp.asarray(seq.key))
            return
        seq.tokens = [int(tok0)]
        self._last_tok[slot] = seq.tokens[0]
        if key2 is not None:
            self._keys = self._keys.at[slot].set(key2)
        self.stats["tokens_generated"] += 1
        self._emit(seq, seq.tokens[0])
        self._maybe_finish(seq, finished)

    def _maybe_finish(self, seq, finished):
        req = seq.request
        t = seq.tokens[-1]
        if req.eos_token_id is not None and t == int(req.eos_token_id):
            self._finish(seq, "stop", finished)
        elif len(seq.tokens) >= int(req.max_new_tokens):
            self._finish(seq, "length", finished)

    def _finish(self, seq, reason, finished):
        if seq.status == "prefilling":
            # cancellation / deadline expiry mid-chunk: out of the
            # chunk pipeline before the slot teardown below frees (or
            # donates) the partially installed block chain
            self.scheduler.leave_prefill(seq)
        seq.status = "finished"
        seq.finish_reason = reason
        seq.t_finish = self._stamp_now()
        tr = self._tr()
        if tr is not None:
            args = {"finish_reason": reason, "tokens": len(seq.tokens)}
            if seq.trace_accepts:
                args["accept_lens"] = list(seq.trace_accepts)
            self._trace_phase_end(tr, seq, args=args)
            tr.instant("finished", tid=tr.req_tid(seq.request_id),
                       args={"finish_reason": reason})
        slot = seq.slot
        if slot is not None and self._slots[slot] is seq:
            self._slots[slot] = None
            self._last_tok[slot] = 0
            self._donate_and_free(seq, slot)
        if self.prefix_cache is not None and seq.prefix_nodes:
            self.prefix_cache.release(seq.prefix_nodes)
            seq.prefix_nodes = []
        finished.append(seq)
        if self.on_finish is not None:
            self.on_finish(seq)

    def _donate_and_free(self, seq, slot):
        """Slot teardown shared by retirement (:meth:`_finish`) and
        preemption (:meth:`_preempt`) — the ONE place the
        donate-vs-free ownership handoff lives, so the two paths cannot
        silently diverge. Publish BEFORE freeing: the slot's prompt
        rows/blocks are intact (decode only ever appended past them)
        and the sequence's own pins still shield its matched chain from
        eviction during the publish walk.

        With a trie: DONATE the slot's full blocks (ownership handoff,
        zero copies); ``free`` then drops only the undonated private
        tail. The donation range is every row actually written — prompt
        AND generated tokens (a multi-turn resubmission of this
        sequence's assistant text hits these blocks), capped at the
        written row count: the last sampled token's KV is never in the
        cache (it would be appended by the decode tick that never ran),
        and a mid-prefill teardown has only ``prefilled`` valid rows."""
        if self.prefix_cache is not None:
            with self._tspan("donate", args={"slot": slot}):
                written = int(self.cache.lengths[slot])
                content = seq.prompt if not seq.tokens else np.concatenate(
                    [seq.prompt, np.asarray(seq.tokens, np.int32)])
                donated = self.prefix_cache.publish_donate(
                    content[:written], self.cache.slot_block_ids(slot))
                self.cache.free(slot, keep=donated)
        else:
            self.cache.free(slot)

    def _expire_deadlines(self, seqs, finished):
        """Retire every sequence whose deadline has passed. Runs once at
        the top of each step, over the queue (an expired request must
        not claim a slot) and the active slots (a running sequence stops
        paying for decode at the first step boundary past its
        deadline)."""
        now = time.monotonic()
        due = [seq for seq in seqs if not seq.done
               and seq.deadline is not None and now >= seq.deadline]
        if any(seq.status != "queued" for seq in due):
            self._drain("deadline", finished)   # one may finish there
        for seq in due:
            if seq.done:
                continue
            if seq.status == "queued" and not self.scheduler.remove(seq):
                continue
            self.stats["timeouts"] += 1
            self._finish(seq, "timeout", finished)

    def _emit(self, seq, token):
        if seq.t_first_token is None:
            seq.t_first_token = self._stamp_now()
        seq.t_last_token = self._stamp_now()
        if self.on_token is not None:
            self.on_token(seq, token)

    def step(self):
        """Admit + this step's budgeted prefill-chunk grant + decode +
        retire; the grant and the decode tick are ONE device program.
        Returns every sequence this step finished (possibly empty),
        deadline expiries included — queue-side timeouts come back with
        ``slot=None`` and no tokens. Only :meth:`cancel` retires
        outside a step; those surface through ``on_finish`` / the
        Sequence handle alone.

        Fault repair: a :class:`~.kv_cache.PoolExhausted` raised
        anywhere in the step body (block growth on a mis-sized shared
        pool, or injected by a fault plan) is caught HERE — any
        admission left half-done is unwound back to the queue, the
        YOUNGEST slot-holding sequence is preempted by recompute
        (:meth:`_preempt`: its chain donates to the prefix trie, so the
        re-queued prefill is usually a zero-copy trie hit), and the
        step retries without re-admitting. Exhaustion that no
        preemption can repair re-raises. Anything the injected
        ``fault_hook`` raises other than PoolExhausted propagates to
        the driver (the gateway's supervisor).

        The unified step is pipelined one program deep (module
        docstring): this call dispatches program j and accepts program
        j-1, so a token surfaces in the call after the one that
        dispatched its program, and :meth:`has_work` stays true while a
        program is in flight. Sequences that a drain outside a step
        finished (:meth:`cancel` / :meth:`evict` with a program in
        flight) are returned by the next call."""
        t0 = self._clock()
        self._stamp_t = t0
        tr = self._tr()
        sp = tr.span("step", args={"step": self.stats["steps"]}) \
            if tr is not None else None
        co = self._co()
        cost0 = co.snapshot() if co is not None else None
        finished, self._finished_outside = self._finished_outside, []
        self._fenced = (0, 0)
        self._sweep = self._mark(
            "sweep", span=True,
            args=tr and {"queued": len(self.scheduler.queue)})
        # deadline sweep BEFORE admission: an expired queued request
        # must never claim a slot (and a running one stops paying for
        # decode at the first step boundary past its deadline)
        self._expire_deadlines(
            list(self.scheduler.queue)
            + [s for s in self._slots if s is not None], finished)
        admitted = []
        for attempt in range(self.num_slots + 2):
            try:
                if self.fault_hook is not None:
                    self.fault_hook(self)
                if attempt == 0:
                    if self._policy:
                        # policy decisions record through the step's
                        # already-guarded tracer; displace best-effort
                        # work BEFORE admission so the freed slots are
                        # in num_free for this very step's admission
                        self.scheduler.tracer = tr
                        self._policy_preempt(finished)
                    admitted = self.scheduler.admissions(
                        self.cache.num_free,
                        hit_len_fn=self._admission_hit_len
                        if self.prefix_cache is not None else None)
                    if admitted:
                        sweep, self._sweep = self._sweep, None
                        adm = self._mark(
                            "admit", span=True,
                            args=tr and {"n": len(admitted)}, end=sweep,
                            end_args=sweep and {"admitted": len(admitted)})
                        try:
                            self._admit_group(admitted, finished)
                        finally:
                            self._mark("other", end=adm)
                if self._spec or self._mtick:
                    # the synchronous variants: what they dispatched they
                    # fenced, so the call's own duration is the step's
                    variant = self._spec_step if self._spec \
                        else self._multitick_step
                    tokens, chunk_tokens = variant(finished)
                    self._count_step(self._clock() - t0, tokens,
                                     chunk_tokens, self._chunk_rows)
                else:
                    self._unified_step(finished, t0)
                break
            except PoolExhausted:
                # unwind, preempt, retry — no device work was committed
                # for the failed attempt (every raise site runs before
                # its device call), so host bookkeeping is consistent.
                # What an EARLIER step left in flight is fenced and
                # accepted before a slot is torn down under it.
                # whichever phase it was raised in (the fault hook's is
                # the sweep)
                sweep, self._sweep = self._sweep, None
                self._mark("other", end=sweep)
                self._abort_admission(admitted)
                admitted = []
                try:
                    self._drain("pool", finished)
                    repaired = self._preempt_youngest()
                except BaseException:
                    self._leave_step()
                    raise
                if not repaired:
                    self._leave_step()
                    raise
            except BaseException:
                # ANY other failure escaping mid-admission (a real
                # device/runtime error — the crash class the supervisor
                # rebuilds for) must not strand popped-but-uninstalled
                # sequences in limbo: back to the queue they go, where
                # crash recovery's snapshot can see them
                self._abort_admission(admitted)
                self._leave_step()
                raise
        self.stats["steps"] += 1
        self._stamp_t = None
        if co is not None:
            co.set_phase(None)
        retire, self._retire_span = self._retire_span, None
        if tr is not None:
            # counter tracks (ph:"C") on the same timeline as the step
            # spans, so Perfetto graphs cost alongside the phases:
            # KV-pool occupancy + table pressure, and (with the cost
            # observatory on) this step's dispatch/transfer deltas. Each
            # is O(1) or one sum over num_slots (the pool's live / trie
            # split is a walk of every table: that is taken at scrape
            # rate, ``occupancy``), and all are taken before the ``step``
            # span closes: what a traced step costs lies under a span
            pool = self.cache.pool
            tr.counter("kv_blocks", {"used": pool.num_used,
                                     "free": pool.num_free})
            tr.counter("block_table_fill",
                       {"fill": round(self.cache.table_fill(), 6)})
            if co is not None:
                d = co.delta(cost0)
                tr.counter("dispatches",
                           {"per_step": d["dispatches"],
                            "compiles": d["compiles"]})
                tr.counter("transfer_bytes",
                           {"h2d": d["h2d_bytes"],
                            "d2h": d["d2h_bytes"]})
            # the tokens of the program this step FENCED (the one it
            # dispatched is counted by the step that fences it)
            step_tokens, chunk_tokens = self._fenced
            # ``retire`` ends where ``step`` does: spans of a lane nest
            t1 = tr.now()
            if retire is not None:
                retire.end(t1=t1)
            sp.end({"tokens": step_tokens, "chunks": chunk_tokens > 0},
                   t1=t1)
        elif retire is not None:    # a capture that began inside the step
            retire.end()
        return finished

    def _leave_step(self):
        """An exception leaves :meth:`step`: stamps must read a fresh
        clock, and the spans it held open are dropped unrecorded."""
        self._stamp_t = None
        self._sweep = self._retire_span = None

    # ----------------------------------------------------- fault recovery
    def _abort_admission(self, seqs):
        """Unwind a half-done admission after a step-body failure:
        every popped sequence not yet installed goes back to the queue
        HEAD in its original FIFO order (by ``queue_tick`` — the batch
        itself arrives suffix-sorted, so arrival order must come from
        the stamp), its claimed slot freed (partial block growth
        included — ``free`` drops exactly the owned tail) and its
        prefix pins released, so ``num_free`` and the pool refcounts
        are exactly what they were before the attempt."""
        tr = self._tr()
        for seq in sorted(seqs, key=lambda s: -s.queue_tick):
            if seq.status != "queued":
                continue      # installed (running/prefilling) — keep
            if self.prefix_cache is not None and seq.prefix_nodes:
                self.prefix_cache.release(seq.prefix_nodes)
                seq.prefix_nodes = []
            seq.prefix_hit_tokens = 0
            if seq.slot is not None:
                if self._slots[seq.slot] is None:
                    self.cache.free(seq.slot)
                seq.slot = None
            if seq.trace_phase == "prefill":
                # the admission this step ran was unwound: back to a
                # fresh queued span (the aborted attempt stays visible
                # as the closed span that preceded it)
                if tr is not None:
                    tr.instant("admission_aborted",
                               tid=tr.req_tid(seq.request_id))
                seq.trace_phase = "queued"
                seq.trace_mark = tr.now() if tr is not None else None
            self.scheduler.requeue_front(seq)

    def _class_slot_usage(self):
        """Running-count-per-class-name ledger for the policy
        scheduler's headroom math: a walk of the slot array (prefilling
        sequences hold slots and count — a reservation is about slot
        occupancy, not decode state)."""
        used = {}
        for seq in self._slots:
            if seq is None or seq.done:
                continue
            pclass = getattr(seq, "pclass", None)
            if pclass is not None:
                used[pclass.name] = used.get(pclass.name, 0) + 1
        return used

    def _policy_preempt(self, finished):
        """SLO-driven preemption (README "Multi-tenant SLO serving"):
        when queued requests have burned past the urgency fraction of
        their TTFT budget and free slots cannot cover them, displace
        one strictly-lower-rank running sequence per uncovered urgent
        request through the ordinary preemption-by-recompute path
        (:meth:`_preempt` — chain donated to the trie, PRNG walk
        snapshotted, stream byte-identical after restore). Runs before
        admission at the top of the step, inside the step's stamp
        window, so urgency and victim choice replay deterministically
        under an injected clock. With nothing below the urgent rank in
        the slots, the request keeps waiting — equals never displace
        equals."""
        urgent = self.scheduler.urgent(self._stamp_t)
        if not urgent:
            return
        if len(urgent) > self.cache.num_free:
            # a victim's slot is torn down below: first fence and accept
            # what is in flight (its finishes may free the slots needed)
            self._drain("preempt", finished)
        free = self.cache.num_free
        tr = self._tr()
        for seq in urgent[free:]:
            pclass = getattr(seq, "pclass", None)
            rank = pclass.rank if pclass is not None else 0
            victims = select_victims(self._slots, 1, rank)
            if not victims:
                continue
            victim = victims[0]
            self.stats["policy_preemptions"] += 1
            if tr is not None:
                tr.instant(
                    "policy_preempt",
                    args={"urgent": seq.request_id,
                          "victim": victim.request_id,
                          "victim_class": getattr(
                              victim.pclass, "name", None)})
            if self.on_policy_preempt is not None:
                self.on_policy_preempt(victim)
            self._preempt(victim)

    def _preempt_youngest(self) -> bool:
        """PoolExhausted repair: displace the YOUNGEST slot-holding
        sequence (latest arrival — the one with the least sunk work and
        the least head-of-line seniority). Returns False when no slot
        holds a preemptible sequence."""
        victims = [s for s in self._slots if s is not None and not s.done]
        if not victims:
            return False
        self._preempt(max(victims, key=lambda s: s.request_id))
        return True

    def _displace(self, seq, reason):
        """Slot teardown shared by preemption (:meth:`_preempt`) and
        cross-engine eviction (:meth:`evict`) — free the sequence's
        slot NOW, donating its written chain (prompt + generated
        blocks) to the prefix trie when one is on, exactly like
        retirement, and snapshot the slot's CURRENT PRNG key — what the
        next decode tick would have sampled with — so the recomputed
        continuation resumes the identical walk. A mid-recompute
        (prefilling, ``restore_point > 0``) sequence keeps the snapshot
        it already carries: its key was never installed into the slot
        array. Leaves the sequence slotless and un-queued; the caller
        decides which engine's :meth:`restore` re-admits it."""
        slot = seq.slot
        tr = self._tr()
        if tr is not None:
            self._trace_phase_end(
                tr, seq, args={reason: True,
                               "tokens": len(seq.tokens)})
            tr.instant(reason, tid=tr.req_tid(seq.request_id),
                       args={"slot": slot})
        if seq.status == "prefilling":
            self.scheduler.leave_prefill(seq)
        if seq.tokens and seq.status == "running":
            seq.key = np.asarray(self._keys, np.uint32)[slot].copy()
        self._slots[slot] = None
        self._last_tok[slot] = 0
        self._donate_and_free(seq, slot)
        if self.prefix_cache is not None and seq.prefix_nodes:
            self.prefix_cache.release(seq.prefix_nodes)
            seq.prefix_nodes = []
        seq.slot = None

    def _preempt(self, seq, reason="preempt"):
        """Preemption-by-recompute: displace the sequence
        (:meth:`_displace` — chain donated, PRNG snapshotted) and
        re-queue it HERE via :meth:`restore`. Because the chain was
        just donated, the recompute prefill is typically a zero-copy
        trie hit; the PRNG walk snapshot keeps the continuation
        byte-identical. Nothing is emitted and the sequence does not
        finish — consumers just see a pause. A model with recurrent
        layers recomputes from position 0 whatever the trie holds (its
        state cannot be resumed from a donated chain), counted in
        ``serving_state_restarts_total{reason}``."""
        self.stats["preemptions"] += 1
        if self._stateful:
            self.stats["state_restarts_" + reason] += 1
        self._displace(seq, "preempted")
        self.restore(seq)
        seq.trace_phase = "preempted"   # restore() named it "recovered"

    def evict(self, seq: Sequence) -> bool:
        """Remove a LIVE sequence from this engine for cross-engine
        migration (the fleet's live request migration / drain path):
        same displacement as preemption — chain donated to THIS
        engine's trie, PRNG walk snapshotted — but ownership leaves
        the engine: the caller re-admits via a SIBLING engine's
        :meth:`restore`, which rebuilds KV by recompute so the
        continuation is byte-identical on the new engine. A
        still-queued sequence is simply removed from the scheduler
        (nothing to displace). Must be called from the thread driving
        :meth:`step`. Returns False for a finished sequence or one
        this engine does not hold."""
        if seq.done:
            return False
        if seq.status == "queued":
            return self.scheduler.remove(seq)
        if seq.slot is None or self._slots[seq.slot] is not seq:
            return False
        self._drain("evict")
        if seq.done:                # the token in flight was its last
            return False
        self._displace(seq, "evicted")
        seq.status = "queued"   # slotless, awaiting the target restore
        return True

    def restore(self, seq: Sequence) -> bool:
        """Re-enqueue a LIVE sequence for recovery-by-recompute (crash
        recovery and preemption both land here): its prompt and
        generated-so-far tokens are known host-side, so its KV is
        rebuilt by prefilling ``prompt + tokens[:-1]`` — chunked when
        long, and often a zero-copy prefix-trie hit on a donated chain
        — after which decode resumes from the last generated token with
        the saved PRNG walk. Greedy streams continue byte-identically
        (the recompute reproduces the exact logits), consumers never
        see a replayed token, and a pre-token sequence simply requeues.
        The caller must have torn down any slot state first (crash
        recovery starts from a fresh engine; :meth:`_preempt` frees the
        slot). Returns False for an already-finished sequence."""
        if seq.done:
            return False
        seq.status = "queued"
        seq.slot = None
        seq.prefix_nodes = []
        seq.prefix_hit_tokens = 0
        seq.prefilled = 0
        seq.restore_point = len(seq.tokens)
        tr = self._tr()
        # the wait-until-readmission span: "recovered" (the gateway
        # restoring onto a rebuilt engine lands here directly);
        # _preempt renames its own restores to "preempted" right after
        # this call. The name tracks state even with tracing off.
        seq.trace_phase = "recovered"
        seq.trace_mark = tr.now() if tr is not None else None
        if seq.tokens:
            seq.work = np.concatenate(
                [seq.prompt, np.asarray(seq.tokens[:-1], np.int32)])
        else:
            seq.work = seq.prompt
        self.stats["restores"] += 1
        self.scheduler.submit(seq)
        return True

    def _count_step(self, dt, tokens, chunk_tokens, rows):
        """Book one FENCED step program: its packed size ``rows``
        (``serving_step_programs_total{rows}``), its tokens by kind
        (``serving_step_tokens_total{kind}``: chunk tokens are prefill,
        the rest are decode rows and their fused ticks) and what it cost
        (:meth:`_record_step`). The synchronous variants pass the call's
        own duration; the pipelined unified step passes the interval from
        the previous fence to this one, which is what a step costs while
        the host's share of it overlaps the chip's."""
        self.stats[program_stat(rows)] += tokens > 0
        self.stats["step_prefill_tokens"] += chunk_tokens
        self.stats["step_decode_tokens"] += tokens - chunk_tokens
        self._fenced = (tokens, chunk_tokens)
        self._record_step(dt, tokens, chunk_tokens > 0,
                          rows == self._chunk_rows)
        if self.on_step is not None:
            self.on_step(float(dt))

    def _record_step(self, dt, tokens, had_chunks, carries_chunks):
        """Feed the step's measured duration + processed tokens into
        the stats surface (``serving_step_duration_seconds`` /
        ``serving_step_tokens`` on /metrics read exactly these) and
        into the headroom EWMAs the adaptive chunk budget derives
        from. ``carries_chunks``: whether the step ran the program that
        carries chunks. The decode baseline is "what a chunk step would
        cost without its chunk", so only that program may feed it: the
        unified step's decode-only program is a smaller one, whose time
        says nothing of the large program's floor (a grant reckoned
        across the two has no fixed point and decays to one token a
        step)."""
        self.stats["last_step_duration_s"] = float(dt)
        self.stats["last_step_tokens"] = int(tokens)
        if tokens <= 0 or dt <= 0:
            return
        a = 0.2
        if had_chunks:
            # packed-step throughput: what a chunk-carrying unified
            # step actually moves per second. Decode-only steps must
            # NOT feed this — their tokens/s is an autoregressive
            # rate, ~budget-fold below what the packed buffer absorbs
            tps = tokens / dt
            self._tps_ewma = tps if self._tps_ewma is None \
                else (1 - a) * self._tps_ewma + a * tps
            self.stats["headroom_tps"] = self._tps_ewma
        elif carries_chunks:
            self._dt_decode_ewma = dt if self._dt_decode_ewma is None \
                else (1 - a) * self._dt_decode_ewma + a * dt

    def _prefill_budget(self):
        """This step's chunk-token grant: the measured-headroom budget
        (``headroom_tps x headroom_mult x decode-only step time``,
        minus the decode rows sharing the step), clamped to
        ``[1, prefill_chunk]`` — i.e. spend at most ~``headroom_mult``
        decode-steps' worth of measured time on the packed buffer, so
        chunk work throttles itself exactly when chunk-carrying steps
        run slower than the decode baseline. Before both EWMAs have a
        measurement — or with ``headroom_mult=None`` — the grant is
        the fixed cap; under a SUSTAINED all-chunk
        regime the decode baseline is the last chunk-free step
        measured (decode-only steps are its only feed), so a backlog
        that never leaves the engine a chunk-free step keeps the fixed
        cap rather than inventing a baseline. The baseline is fed only
        by chunk-free steps of the program that carries chunks
        (:meth:`_record_step`): the multi-tick and the speculative step
        have one program and feed it; the unified step runs its
        chunk-free steps at the smaller packed size, so there the
        baseline stays unfed and the grant is the cap (with two sizes a
        smaller grant would save attention time only: the large
        program's dense cost is fixed). Sub-block grants are
        not wasted: the scheduler carries them to the next plan
        (``FIFOScheduler.prefill_plan``)."""
        cap = self._chunk
        if self._headroom_mult is None or self._tps_ewma is None \
                or self._dt_decode_ewma is None:
            self.stats["headroom"] = cap
            return cap
        n_dec = sum(1 for s in self._slots
                    if s is not None and s.status == "running")
        afford = int(self._tps_ewma * self._headroom_mult
                     * self._dt_decode_ewma) - n_dec
        budget = max(1, min(cap, afford))
        self.stats["headroom"] = budget
        return budget

    def _unified_step(self, finished, t0):
        """ONE device call for everything this step advances: every
        running slot contributes a span-1 decode row and every planned
        prefill chunk a span-n row to the packed token buffer of the
        unified ragged program (``decode.build_ragged_step_fn``): a
        mixed step launches one program, and a mid-prefill slot costs
        its chunk span, not a full-length decode row. Pure-decode
        steps still fuse ``choose_num_steps`` ticks (the scan tail of
        the same program).

        Pipelined one program deep: plan and dispatch program j, THEN
        fence program j-1 (``device-wait``) and accept its tokens
        (``host-accept``). The plan counts what j-1 will have advanced
        (:meth:`_decode_candidates`; chunk progress moves at dispatch),
        a decode row whose last token is still on the device takes it
        there (``take``), and nothing between ``plan`` and ``dispatch``
        reads an output of j-1. With nothing to dispatch the call only
        fences (an ``idle`` drain); with nothing in flight it only
        dispatches. ``t0`` is the step's start reading of the clock."""
        tr = self._tr()
        sp = self._mark_plan()
        prev = self._inflight
        plan = []
        if self._chunk and self.scheduler.num_prefilling:
            plan = self.scheduler.prefill_plan(self._prefill_budget(),
                                               self.cache.block_size,
                                               cap=self._chunk)
        cands = self._decode_candidates()
        if not cands and not plan:
            plan_args = sp and {"rows": 0, "chunks": 0}
            if prev is None:
                self._mark_retire(end=sp, end_args=plan_args)
            else:
                self._mark("other", end=sp, end_args=plan_args)
                self._drain("idle", finished, retire=True)
            return
        n = self.scheduler.choose_num_steps(
            [c[1] for c in cands], budgets=[c[4] for c in cands]) \
            if cands else 1
        # the packed size follows the plan: a step that carries no chunk
        # runs the program at the decode rows alone. Everything handed from
        # program to program (``tok_fin``, the keys, the pool, the state
        # store) is by slot or pool-shaped, so either size may follow, or
        # be dispatched behind, the other
        R = self.num_slots
        T = self._token_budget if plan else self._decode_rows
        ids = np.zeros(T, np.int32)
        seg = np.full(T, R, np.int32)       # sentinel: dead packed rows
        pos = np.zeros(T, np.int32)
        qstart = np.zeros(R, np.int32)
        qlen = np.zeros(R, np.int32)
        kvlen = np.zeros(R, np.int32)
        dec_mask = np.zeros(R, np.int32)
        temps = np.zeros(R, np.float32)
        topks = np.zeros(R, np.int32)
        take = np.zeros(R, np.int32)
        chunk_keys = np.zeros((R, 2), np.uint32)
        rows, cursor = self._pack_decode_rows(
            cands, n, ids, seg, pos, qstart, qlen, kvlen, dec_mask, temps,
            topks, take=take)
        chunk_rows, cursor = self._pack_chunk_rows(
            plan, cursor, ids, seg, pos, qstart, qlen, kvlen, chunk_keys,
            temps, topks)
        # whose advanced key the program stores: decode rows, and a fresh
        # sequence's final chunk (a restored one resumes its own walk)
        adopt = dec_mask.copy()
        for slot, seq, _ntok, final in chunk_rows:
            adopt[slot] = int(final and not seq.restore_point)
        # plan: admission already ran in step(); this is the chunk grant +
        # span packing. launch: dispatch (the jitted call of THIS step's
        # program returns) and device-wait (the host transfer that fences
        # the PREVIOUS program). host-accept: that program's token/chunk
        # bookkeeping (donate spans nest inside it).
        self._mark("other", end=sp,
                   end_args=sp and {"rows": len(rows), "chunks": len(plan),
                                    "fused_steps": n})
        launch = args = None
        if tr is not None:
            # a traced step counts the kernel's grid on the host: under
            # ``launch``, and ``other`` on the driver clock
            launch = tr.span("launch")
            args = self._dispatch_args(
                qstart, qlen, kvlen, T, len(rows), n * len(rows),
                cursor - len(rows))
            args["ahead"] = int(prev is not None)
            args["packed_rows"] = T
        sp = self._mark("dispatch", span=True, args=args)
        co = self._co()
        keys_in = self._keys
        # ``call``: the jitted call alone, from before its arguments are
        # handed over until it returns; what of ``dispatch`` is not under
        # it is the commit below
        with tr.span("call") if tr is not None else NULL_SPAN:
            npk, npv, toks, tok_fin, keys_out, *moe = self._ragged_fn(n, T)(
                self._params, *self.cache.kv_args(),
                self.cache.tables, ids, seg, pos, qstart, qlen, kvlen,
                dec_mask, keys_in, temps, topks,
                self._no_toks if prev is None else prev.tok_fin, take,
                chunk_keys, adopt,
                *((self.cache.store,) if self._stateful else ()))
        # the program is on the device's queue: commit what it advances
        self.cache.update(npk, npv)
        if self._stateful:
            self.cache.store = moe.pop()
            self.stats["state_rows"] += len(rows) + len(chunk_rows)
        self._keys = keys_out
        chunks = []
        for slot, seq, ntok, final in chunk_rows:
            chunks.append((slot, seq, seq.prefilled, ntok, final))
            seq.prefilled += ntok
            if final:       # no further chunk; its decode row comes next
                self.scheduler.leave_prefill(seq)
        self._inflight = _InFlight(
            toks, tok_fin, moe, keys_in, n, rows, chunks, cursor, T,
            None if prev is not None else t0)
        if self._routing is not None:
            # which rows of this program's picks are whose, at which
            # positions (the array itself stays on the device)
            self._routing.note(moe[1], [
                (seq, int(qstart[slot]), int(qlen[slot]),
                 int(kvlen[slot] - qlen[slot]))
                for slot, seq, *_ in rows + chunk_rows])
        self.stats["unified_steps"] += 1
        self.stats["steps_dispatched_ahead"] += prev is not None
        if co is not None:
            # sharded launch: tick 0 all-reduces the PADDED packed
            # buffer (the device computes full shapes, at the size this
            # step ran), each fused tail tick the per-slot row block —
            # exact, shape-derived
            self._record_collectives(co, [(T, 1), (self.num_slots, n - 1)])
        launch_args = launch and {"packed_tokens": cursor, "fused_steps": n}
        if prev is not None:
            self._mark("other", end=sp)
            self._fence(prev, finished, launch, launch_args, retire=True)
        else:
            self._mark_retire(end=sp, outer=launch, outer_args=launch_args)

    def _decode_candidates(self):
        """Every slot the next step program can carry a decode row for,
        reckoning with the program in flight: ``(slot, seq, length,
        take, budget)`` — the KV rows the slot will hold once that
        program is accepted, whether the row's input token is that
        program's output (still on the device), and the tokens the
        sequence may still generate after it. A sequence whose budget the
        token in flight exhausts is left out: its ``length`` finish is
        known ahead of time, so no row is wasted on it. Only an EOS is
        found a step late: that row has then ridden one program too
        many, whose token :meth:`_accept_decode_rows` drops and whose KV
        row lies past the sequence's end, in a block nothing reads
        before rewriting it. With nothing in flight this is the running
        slots as they stand."""
        fl = self._inflight
        ahead = fl.ahead if fl is not None else {}
        lens = self.cache.lengths
        out = []
        for slot, s in enumerate(self._slots):
            if s is None:
                continue
            gained, take = 0, False
            entry = ahead.get(slot)
            if entry is not None and entry[0] is s:
                gained, take = entry[1], entry[2]
            elif s.status != "running":
                continue
            # a final chunk in flight: the slot holds the whole work
            # content once accepted (token 0's own row comes with the
            # decode row that reads it)
            length = int(lens[slot]) + gained if s.status == "running" \
                else s.work_len
            budget = s.remaining - gained
            if budget > 0:
                out.append((slot, s, length, take, budget))
        return out

    def _pack_decode_rows(self, cands, n, ids, seg, pos, qstart, qlen,
                          kvlen, dec_mask, temps, topks, eos_ids=None,
                          budgets=None, take=None):
        """Pack the decode candidates' span-1 rows
        (:meth:`_decode_candidates`) into the packed token buffer — the
        ONE decode-row assembly shared by the unified and multi-tick
        steps (``_pack_chunk_rows``' twin), so the packing and
        table-pre-growth rules cannot silently diverge. Pre-grows each
        slot's table for the fused block: ``n`` rows on the unified scan
        (it appends unconditionally); ``min(n, remaining)`` when the
        alive-mask metadata (``eos_ids``/``budgets``) is being packed,
        because the device stops a row's appends exactly at its
        EOS/budget cut. ``take`` (the pipelined unified step) is set
        where the input token is the in-flight program's output.
        Returns ``(rows, cursor)``: the ``(slot, seq)`` pairs packed and
        the cursor past them."""
        rows, cursor = [], 0
        for slot, s, length, from_device, budget in cands:
            grow = n if budgets is None else min(n, budget)
            self.cache.ensure_capacity(slot, length + grow)
            qstart[slot] = cursor
            qlen[slot] = 1
            kvlen[slot] = length + 1
            dec_mask[slot] = 1
            if from_device:
                take[slot] = 1
            elif s.status == "running":
                ids[cursor] = self._last_tok[slot]
            else:           # restored, its final chunk in flight
                ids[cursor] = s.tokens[-1]
            seg[cursor] = slot
            pos[cursor] = length
            temps[slot] = float(s.request.temperature)
            topks[slot] = int(s.request.top_k)
            if eos_ids is not None:
                eos = s.request.eos_token_id
                eos_ids[slot] = -1 if eos is None else int(eos)
                budgets[slot] = budget
            rows.append((slot, s))
            cursor += 1
        return rows, cursor

    def _fence(self, rec, finished, launch=None, launch_args=None,
               retire=False):
        """Fence one dispatched unified step and accept what it computed:
        ``device-wait`` (the host transfer of its tokens, with the
        routing summary of the same program as the span's args) under
        ``launch`` (the caller's, when this call also dispatched), then
        ``host-accept``. With ``retire`` the fence is the last thing its
        step does and ``retire`` follows; else the thread goes back to the
        phase it came from (a drain outside a step, the pool repair, or a
        deadline drain inside the ``sweep``, whose span stops here and
        goes on after). An exception out of the program surfaces here;
        what was in flight is then dropped, never half accepted."""
        pc = self.driver_clock
        back = pc.phase if pc is not None else None     # in a step or not
        sweep, self._sweep = self._sweep, None
        in_sweep = sweep is not None or back == "sweep"
        if in_sweep:
            self._mark("other", end=sweep)      # before ``launch`` opens
        if launch is None:
            launch = self._tspan("launch")
        sp = self._mark("device-wait", span=True)
        try:
            toks_np = np.asarray(rec.toks)          # [n, R]
            moe = self._count_moe(rec.moe)
        except BaseException:
            self._abandon(rec)
            raise
        now = self._clock()
        base = rec.t_base if rec.t_base is not None else self._t_fence
        self._t_fence = now
        # device-wait closes with the routing of the step it fenced
        sp = self._mark("host-accept", span=True, end=sp, end_args=moe,
                        outer=launch, outer_args=launch_args)
        n, rows = rec.n, rec.rows
        # chunk bookkeeping first: a final chunk's _install_seq emits
        # its token 0 before this step's decode rows surface theirs
        for slot, seq, off, ntok, final in rec.chunks:
            if seq.status == "prefilling" and self._slots[slot] is seq:
                self._advance_chunk(seq, ntok, toks_np[0, slot], None,
                                    finished, offset=off)
        emitted = 0
        if rows:
            self.stats["decode_calls"] += 1
            self.stats["decode_steps"] += n
            self.stats["slot_steps"] += n * self.num_slots
            emitted = self._accept_decode_rows(toks_np, n, rows, finished)
        accept_args = sp and {"emitted": emitted}
        if retire:
            self._mark_retire(end=sp, end_args=accept_args)
        elif in_sweep:
            self._sweep = self._mark("sweep", span=True, end=sp,
                                     end_args=accept_args)
        else:
            self._mark(back, end=sp, end_args=accept_args)
        self._count_step(now - base, rec.packed + (n - 1) * len(rows),
                         rec.packed - len(rows), rec.size)

    def _drain(self, reason, finished=None, retire=False):
        """Fence and accept the program in flight, if any: what every
        path that changes slots outside plan -> accept calls first, so
        that it sees (and tears down) only accepted state. Sequences the
        accept finishes go to ``finished``; outside a step they are kept
        for the next :meth:`step` to return. Counted by ``reason``
        (``serving_pipeline_drains_total``; ``snapshot`` is the gateway's
        recovery snapshot)."""
        rec = self._inflight
        if rec is None:
            return
        self._inflight = None
        self._fence(rec, finished if finished is not None
                    else self._finished_outside, retire=retire)
        self.stats["drains_" + reason] += 1     # a fence that raised
        # counted itself, as ``fault``

    def _abandon(self, rec):
        """A fence raised: drop ``rec`` and anything dispatched behind it
        without accepting a token. Host state goes back to what was
        accepted: the key state is the one ``rec`` started from (a
        program's keys are ahead of the accepted tokens by what it
        sampled) and chunk progress returns to the first dropped chunk's
        offset. The rows the dropped programs wrote to the KV pool lie past
        every accepted length, and a pool row is safe to write twice. A
        recurrent state is not: the dropped programs have applied their
        tokens to it, and a re-run from the accepted offset would apply
        them a second time. A model with such layers therefore sends every
        live sequence the dropped programs carried back to position 0, by
        the recompute path preemption has (``_preempt``: the slot is freed,
        the sequence queued again with what it has generated, and its first
        span starts from a zero state);
        ``serving_state_restarts_total{reason="fault"}`` counts them."""
        later, self._inflight = self._inflight, None
        self._keys = rec.keys_in
        self.stats["drains_fault"] += 1
        for r in (later, rec):      # the earlier offsets win
            if r is None:
                continue
            for _slot, seq, off, _ntok, final in reversed(r.chunks):
                if seq.status != "prefilling":
                    continue
                seq.prefilled = off
                if final and seq not in self.scheduler.prefilling:
                    self.scheduler.prefilling.appendleft(seq)
        if self._stateful:
            carried = [(slot, seq) for r in (rec, later) if r is not None
                       for slot, seq, *_ in list(r.rows) + list(r.chunks)]
            for slot, seq in dict.fromkeys(carried):
                if not seq.done and self._slots[slot] is seq:
                    self._preempt(seq, reason="fault")

    def _accept_decode_rows(self, toks_np, n, rows, finished,
                            counts=None):
        """Host-accept of the fused ticks' ``[n, R]`` token block —
        the ONE trim loop shared by the unified and multi-tick steps,
        so the accept/trim rules (EOS and budget cuts via
        ``_maybe_finish``, per-token bookkeeping) cannot silently
        diverge. Tick-major like the device computed it. ``rows`` are
        the ``(slot, seq)`` pairs the program was DISPATCHED with: a
        token is accepted only for the sequence it was computed for,
        still running in that slot — by the time a pipelined step is
        accepted the slot may be free or hold another sequence (an EOS
        found a step late, then an admission), and a sequence that
        finished at an earlier tick is skipped from then on (on the
        multi-tick path the device's alive cut equals this trim, so the
        skipped entries are masked garbage that never surfaces).
        ``counts`` (optional [R] array) receives each slot's
        accepted-token count — the multi-tick key-walk adoption index.
        Returns tokens emitted."""
        emitted = 0
        for i in range(n):
            for slot, seq in rows:
                if seq.status != "running" or self._slots[slot] is not seq:
                    continue
                if i == 0:
                    seq.launches += 1   # rode this step's one program
                t = int(toks_np[i, slot])
                seq.tokens.append(t)
                if counts is not None:
                    counts[slot] += 1
                self.cache.lengths[slot] += 1
                self._last_tok[slot] = t
                self.stats["active_slot_steps"] += 1
                self.stats["tokens_generated"] += 1
                emitted += 1
                self._emit(seq, t)
                self._maybe_finish(seq, finished)
        return emitted

    def _multitick_step(self, finished):
        """ONE device call that advances every slot by up to
        ``decode_ticks`` tokens (README "Multi-tick decode"): the
        unified ragged step with the per-token host round-trip
        amortized to one sync per ``n`` ticks. Every running slot
        contributes a span-1 decode row and every planned prefill
        chunk its span to the packed tick-0 buffer, exactly like
        :meth:`_unified_step`; the fused tail then runs ``n``
        (scheduler-chosen, runtime — one compilation serves them all)
        decode ticks with ON-DEVICE EOS/budget retirement: a finished
        row's appends drop inside the program precisely where the
        host's trim will cut, and the program returns early once
        every row is dead. The host accepts the whole ``[n, R]``
        token block in one ``host-accept``, trimming each slot at its
        first EOS/budget cut — byte-identical to tick-at-a-time —
        and adopts each surviving row's PRNG key at its trim cut from
        the returned key walk. Returns ``(tokens_processed,
        chunk_tokens)`` as :meth:`_unified_step` does."""
        tr = self._tr()
        sp = self._mark_plan()
        co = self._co()
        plan = []
        if self._chunk and self.scheduler.num_prefilling:
            plan = self.scheduler.prefill_plan(self._prefill_budget(),
                                               self.cache.block_size,
                                               cap=self._chunk)
        cands = self._decode_candidates()   # nothing is ever in flight
        active = [c[1] for c in cands]      # here: the running slots
        if not active and not plan:
            self._mark_retire(end=sp,
                              end_args=sp and {"rows": 0, "chunks": 0})
            return 0, 0
        n = self.scheduler.choose_decode_ticks(active,
                                               self._decode_ticks)
        R, T = self.num_slots, self._token_budget
        ids = np.zeros(T, np.int32)
        seg = np.full(T, R, np.int32)       # sentinel: dead packed rows
        pos = np.zeros(T, np.int32)
        qstart = np.zeros(R, np.int32)
        qlen = np.zeros(R, np.int32)
        kvlen = np.zeros(R, np.int32)
        dec_mask = np.zeros(R, np.int32)
        temps = np.zeros(R, np.float32)
        topks = np.zeros(R, np.int32)
        eos_ids = np.full(R, -1, np.int32)  # -1: no EOS configured
        budgets = np.zeros(R, np.int32)
        keys = np.asarray(self._keys, np.uint32).copy()
        # packing eos_ids/budgets switches _pack_decode_rows to the
        # alive-mask pre-growth: the WHOLE block's capacity up front
        # (min(n, remaining) rows — the device stops at the cut), so
        # no mid-block host intervention, no fallback at block
        # boundaries
        rows, cursor = self._pack_decode_rows(
            cands, n, ids, seg, pos, qstart, qlen, kvlen, dec_mask, temps,
            topks, eos_ids=eos_ids, budgets=budgets)
        chunk_rows, cursor = self._pack_chunk_rows(
            plan, cursor, ids, seg, pos, qstart, qlen, kvlen, keys,
            temps, topks)
        chunk_tokens = cursor - len(active)
        self._mark("other", end=sp,
                   end_args=sp and {"rows": len(active), "chunks": len(plan),
                                    "ticks": n})
        launch = args = None
        if tr is not None:
            launch = tr.span("launch")
            args = self._dispatch_args(
                qstart, qlen, kvlen, T, len(active), n * len(active),
                chunk_tokens)
        sp = self._mark("dispatch", span=True, args=args)
        with tr.span("call") if tr is not None else NULL_SPAN:
            npk, npv, toks, kwalk, ticks_run = self._mtick_fn()(
                self._params, *self.cache.kv_args(),
                self.cache.tables, ids, seg, pos, qstart, qlen, kvlen,
                dec_mask, keys, temps, topks, eos_ids, budgets,
                np.int32(n))
        self.cache.update(npk, npv)
        sp = self._mark("device-wait", span=True, end=sp)
        toks_np = np.asarray(toks)          # [max_ticks, R]
        kwalk_np = np.asarray(kwalk)        # [max_ticks, R, 2]
        ticks = int(ticks_run)              # <= n: early exit when all
        self.stats["unified_steps"] += 1    # rows retire on device
        if co is not None:
            # multi-tick sharded launch: tick 0 on the padded packed
            # buffer + the ticks the while_loop ACTUALLY ran (early
            # exit spends no wire) on the per-slot row block
            self._record_collectives(
                co, [(self._token_budget, 1),
                     (self.num_slots, ticks - 1)])
        sp = self._mark(
            "host-accept", span=True, end=sp, outer=launch,
            outer_args=launch and {"packed_tokens": cursor, "ticks": n,
                                   "ticks_run": ticks})
        # chunk bookkeeping first — mirrors the unified-step order (a
        # final chunk adopts tick 0's token/key, the same one split as
        # a one-shot prefill)
        for slot, seq, ntok, final in chunk_rows:
            self._advance_chunk(seq, ntok, toks_np[0, slot],
                                kwalk_np[0, slot], finished)
        emitted_total = 0
        if active:
            self.stats["decode_calls"] += 1
            self.stats["decode_steps"] += ticks
            self.stats["slot_steps"] += ticks * self.num_slots
            self.stats["mtick_syncs"] += 1
            self.stats["mtick_ticks"] += ticks
            self.stats["last_decode_ticks"] = ticks
            counts = np.zeros(R, np.int32)  # accepted tokens per slot
            emitted_total = self._accept_decode_rows(
                toks_np, ticks, rows, finished, counts=counts)
            # adopt each SURVIVING decode row's key at its trim cut:
            # keys_walk[m - 1] for a row that accepted m tokens (a
            # still-running row accepted every tick, so this is the
            # post-block key — same walk position as m sequential
            # ticks). Finished slots are freed; idle/chunk rows keep
            # their host key state. Snapshot AFTER chunk bookkeeping:
            # a final chunk's _install_seq key write must survive.
            knp = np.asarray(self._keys, np.uint32).copy()
            adopted = False
            for slot in range(self.num_slots):
                seq = self._slots[slot]
                if seq is None or not dec_mask[slot] \
                        or seq.status != "running" \
                        or counts[slot] == 0:
                    continue
                knp[slot] = kwalk_np[counts[slot] - 1, slot]
                adopted = True
            if adopted:
                self._keys = jnp.asarray(knp)
        self._mark_retire(end=sp,
                          end_args=sp and {"emitted": emitted_total,
                                           "ticks_run": ticks})
        return chunk_tokens + emitted_total, chunk_tokens

    def _pack_chunk_rows(self, plan, cursor, ids, seg, pos, qstart, qlen,
                         kvlen, keys, temps, topks, sample_start=None):
        """Pack this step's planned prefill chunks into the packed
        token buffer — the ONE chunk-row assembly shared by the
        unified and speculative steps, so their packing rules (block
        growth, span metadata, the final-chunk-only sampling rule)
        cannot silently diverge. ``sample_start`` is the speculative
        program's extra metadata: a chunk row samples at its span END
        (token 0); the unified program derives that position in-program
        and passes None. Returns ``(chunk_rows, cursor)``."""
        chunk_rows = []                     # (slot, seq, n_tokens, final)
        for seq, ntok in plan:
            slot, off = seq.slot, seq.prefilled
            self.cache.ensure_capacity(slot, off + ntok)
            final = off + ntok == seq.work_len
            qstart[slot] = cursor
            qlen[slot] = ntok
            kvlen[slot] = off + ntok
            if sample_start is not None:
                sample_start[slot] = cursor + ntok - 1
            ids[cursor:cursor + ntok] = seq.work[off:off + ntok]
            seg[cursor:cursor + ntok] = slot
            pos[cursor:cursor + ntok] = np.arange(off, off + ntok,
                                                  dtype=np.int32)
            # chunk rows sample (and advance the PRNG) only on their
            # FINAL chunk, so streams stay byte-identical to a one-shot
            # prefill
            keys[slot] = np.asarray(seq.key)
            if final:
                temps[slot] = float(seq.request.temperature)
                topks[slot] = int(seq.request.top_k)
            chunk_rows.append((slot, seq, ntok, final))
            cursor += ntok
        return chunk_rows, cursor

    def _spec_step(self, finished):
        """ONE device call for everything a speculative step advances
        (README "Speculative decoding"): every running slot contributes
        a DRAFT-EXTENDED verify span — ``[last_token, d_1 .. d_k]``,
        the drafter's guesses appended through the block tables exactly
        like a prefill chunk — and every planned prefill chunk its
        span, to the packed buffer of the verify program
        (``decode.build_spec_verify_fn``). The program samples
        ``spec_k + 1`` consecutive positions per row under the standard
        split-per-token PRNG walk; the host accepts the longest draft
        prefix the target model reproduced, emits those tokens plus the
        model's own correction at the first mismatch (so every launch
        yields >= 1 token and acceptance only reorders work — streams
        are byte-identical to speculation off, greedy AND sampled), and
        rolls rejected draft K/V back by truncating the slot's private
        block tail (``PagedKVCache.truncate`` — exact num_free/refcount
        restoration, donated trie blocks untouched).

        Budget discipline: drafts share the packed buffer's headroom
        with the chunk grant (``FIFOScheduler.spec_grants`` — a verify
        span spends ``1 + k`` positions), so chunk-heavy steps throttle
        speculation instead of overflowing the compile geometry.
        Returns ``(tokens_processed, chunk_tokens)`` as
        :meth:`_unified_step` does."""
        tr = self._tr()
        sp = self._mark_plan()
        co = self._co()
        plan = []
        if self._chunk and self.scheduler.num_prefilling:
            plan = self.scheduler.prefill_plan(self._prefill_budget(),
                                               self.cache.block_size,
                                               cap=self._chunk)
        active = [(slot, s) for slot, s in enumerate(self._slots)
                  if s is not None and s.status == "running"]
        if not active and not plan:
            self._mark_retire(end=sp,
                              end_args=sp and {"rows": 0, "chunks": 0})
            return 0, 0
        R, T = self.num_slots, self._spec_budget
        lens = self.cache.lengths
        chunk_spend = sum(n for _, n in plan)
        # drafter proposals, clipped per row to the verify depth, the
        # token budget (a verify emits at most k+1 tokens — proposing
        # past remaining-1 is wasted span), and the KV capacity
        drafts = []
        for slot, s in active:
            cap = min(self._spec_k, s.remaining - 1,
                      self.max_seq_len - int(lens[slot]) - 1)
            d = self.drafter.propose(s, cap) if cap > 0 else ()
            drafts.append(np.asarray(d, np.int32).reshape(-1)[:max(cap, 0)])
        grants = self.scheduler.spec_grants(
            [len(d) for d in drafts], T - R - chunk_spend)
        ids = np.zeros(T, np.int32)
        seg = np.full(T, R, np.int32)       # sentinel: dead packed rows
        pos = np.zeros(T, np.int32)
        qstart = np.zeros(R, np.int32)
        qlen = np.zeros(R, np.int32)
        kvlen = np.zeros(R, np.int32)
        sample_start = np.zeros(R, np.int32)
        temps = np.zeros(R, np.float32)
        topks = np.zeros(R, np.int32)
        keys = np.asarray(self._keys, np.uint32).copy()
        cursor = 0
        verify_rows = []                    # (slot, seq, draft, len0)
        for (slot, s), d, g in zip(active, drafts, grants):
            d = d[:g]
            L0 = int(lens[slot])
            q = 1 + len(d)
            # the verify span appends draft K/V rows [L0, L0+q) — the
            # table must cover them pre-call (rejected rows hand their
            # blocks back through truncate below)
            self.cache.ensure_capacity(slot, L0 + q)
            qstart[slot] = cursor
            qlen[slot] = q
            kvlen[slot] = L0 + q
            sample_start[slot] = cursor     # sample EVERY span position
            ids[cursor] = self._last_tok[slot]
            if len(d):
                ids[cursor + 1:cursor + q] = d
            seg[cursor:cursor + q] = slot
            pos[cursor:cursor + q] = np.arange(L0, L0 + q, dtype=np.int32)
            temps[slot] = float(s.request.temperature)
            topks[slot] = int(s.request.top_k)
            verify_rows.append((slot, s, d, L0))
            cursor += q
        chunk_rows, cursor = self._pack_chunk_rows(
            plan, cursor, ids, seg, pos, qstart, qlen, kvlen, keys,
            temps, topks, sample_start=sample_start)
        self._mark("other", end=sp,
                   end_args=sp and {"rows": len(active), "chunks": len(plan),
                                    "draft_tokens": int(sum(grants))})
        launch = args = None
        if tr is not None:
            launch = tr.span("launch")
            args = self._dispatch_args(
                qstart, qlen, kvlen, T, len(verify_rows),
                cursor - chunk_spend, chunk_spend)
        sp = self._mark("dispatch", span=True, args=args)
        with tr.span("call") if tr is not None else NULL_SPAN:
            npk, npv, toks, kwalk = self._spec_fn()(
                self._params, *self.cache.kv_args(),
                self.cache.tables, ids, seg, pos, qstart, qlen, kvlen,
                sample_start, keys, temps, topks)
        self.cache.update(npk, npv)
        sp = self._mark("device-wait", span=True, end=sp)
        toks_np = np.asarray(toks)          # [spec_len, R]
        kwalk_np = np.asarray(kwalk)        # [spec_len, R, 2]
        self.stats["spec_steps"] += 1
        if co is not None:
            # one packed verify forward per spec step (no decode tail)
            self._record_collectives(co, [(self._spec_budget, 1)])
        sp = self._mark("host-accept", span=True, end=sp, outer=launch,
                        outer_args=launch and {"packed_tokens": cursor})
        # chunk bookkeeping first — mirrors the unified-step order (a
        # final chunk adopts its walk-step-0 token/key, the same one
        # split as a one-shot prefill)
        for slot, seq, ntok, final in chunk_rows:
            self._advance_chunk(seq, ntok, toks_np[0, slot],
                                kwalk_np[0, slot], finished)
        emitted_total = 0
        accept_lens = []
        if verify_rows:
            self.stats["decode_calls"] += 1
            self.stats["decode_steps"] += 1
            self.stats["slot_steps"] += self.num_slots
            # snapshot AFTER chunk bookkeeping: a final chunk's
            # _install_seq key write must survive the batched update
            knp = np.asarray(self._keys, np.uint32).copy()
            for slot, seq, d, L0 in verify_rows:
                seq.launches += 1       # rode this step's one verify
                a = 0
                while a < len(d) and int(toks_np[a, slot]) == int(d[a]):
                    a += 1
                req = seq.request
                emit = []
                for j in range(a + 1):
                    t = int(toks_np[j, slot])
                    emit.append(t)
                    if req.eos_token_id is not None \
                            and t == int(req.eos_token_id):
                        break       # sequential decode would stop here
                    if len(seq.tokens) + len(emit) \
                            >= int(req.max_new_tokens):
                        break
                m = len(emit)
                # rollback: rows [L0, L0 + 1 + len(d)) were written;
                # only [L0, L0 + m) are confirmed — the last emitted
                # token's own KV is at L0 + m, NOT in the cache, which
                # preserves the donation invariant
                self.cache.truncate(slot, L0 + m)
                self.cache.lengths[slot] = L0 + m
                self._last_tok[slot] = emit[-1]
                knp[slot] = kwalk_np[m - 1, slot]
                self.stats["spec_proposed"] += len(d)
                self.stats["spec_accepted"] += m - 1
                self.stats["spec_tokens"] += m
                accept_lens.append(m)
                if tr is not None and len(seq.trace_accepts) < 512:
                    # per-request acceptance history, surfaced as the
                    # decode span's args at retirement (bounded so a
                    # very long decode cannot grow an unbounded list)
                    seq.trace_accepts.append(m)
                emitted_total += m
                for t in emit:
                    seq.tokens.append(t)
                    self.stats["active_slot_steps"] += 1
                    self.stats["tokens_generated"] += 1
                    self._emit(seq, t)
                self._maybe_finish(seq, finished)
            self._keys = jnp.asarray(knp)
        self.stats["spec_last_accept"] = accept_lens
        if tr is not None:
            if verify_rows:
                tr.instant("spec_accept",
                           args={"accept_lens": list(accept_lens),
                                 "proposed": [len(d) for _, _, d, _
                                              in verify_rows]})
        self._mark_retire(end=sp,
                          end_args=sp and {"emitted": emitted_total})
        return chunk_spend + emitted_total, chunk_spend

    def has_work(self) -> bool:
        return bool(self.scheduler.num_queued
                    or self._inflight is not None
                    or any(s is not None for s in self._slots))

    @property
    def num_active(self) -> int:
        """Slots currently decoding (the /metrics active-slots gauge)."""
        return self.num_slots - self.cache.num_free

    # ------------------------------------------------------------- offline
    def generate(self, requests):
        """Submit all, run to completion, return each request's
        :class:`GenerationResult` (array-like generated ids, np.int32,
        EOS included when hit, plus ``finish_reason``) in submission
        order."""
        seqs = [self.submit(r) for r in requests]
        while self.has_work():
            self.step()
        return [GenerationResult(s.output_ids(), s.finish_reason,
                                 s.request_id) for s in seqs]
