"""Block-table paged KV cache for continuous-batching decode.

:class:`PagedKVCache` ("Ragged Paged Attention", PAPERS.md): the
:class:`~.block_manager.BlockManager` pool IS the cache — there is no
per-slot dense array. Each live slot owns a row of a host block table
``[num_slots, max_blocks]`` naming the physical pool blocks that spell
its logical cache; prefix-cache hits install by *referencing* published
block ids (no copy — N holders share one block), decode growth appends
fresh private blocks lazily, and retirement *donates* full blocks to the
trie. Admission claims a free slot, finish releases it, and a freed
block's stale rows are never read (the kernels walk a row's own length).

A token has a row in the pool for the layers that attend over keys and
values; a layer whose cache is a recurrent state (a hybrid model's linear
layers) keeps it in a second store, by slot and not paged
(:attr:`PagedKVCache.state`): one manager, two kinds of cache.

The pool's device arrays are functionally updated (donated through the
jitted writers on non-CPU backends, so XLA updates in place); the host
``lengths`` / ``tables`` mirrors are the scheduling truth — device-side
lengths and tables are re-fed from them every step, so a freed slot
resets by writing host ints, not a device op. The step programs read the
pool through runtime table arguments (``serving/decode.py``), so table
growth, hits and evictions never add traces.
"""
from __future__ import annotations

import functools
import heapq

import jax
import jax.numpy as jnp
import numpy as np


class PoolExhausted(RuntimeError):
    """KV block pool exhausted: live sequences + pinned prefix blocks
    exceed the pool. A RuntimeError subclass for back-compat with
    callers that caught the old untyped raise, but TYPED so the engine
    can catch it and preempt the youngest sequence by recompute
    (``ContinuousBatchingEngine`` donates the victim's chain to the
    prefix trie and re-queues it) instead of taking the server down.
    Carries the pool occupancy snapshot at the failed allocation."""

    def __init__(self, live_blocks=0, pinned_blocks=0, free_blocks=0,
                 message=None):
        self.live_blocks = int(live_blocks)
        self.pinned_blocks = int(pinned_blocks)
        self.free_blocks = int(free_blocks)
        super().__init__(message or (
            f"KV block pool exhausted: live sequences + pinned prefix "
            f"blocks exceed the pool (live={self.live_blocks}, "
            f"pinned={self.pinned_blocks}, free={self.free_blocks}); "
            f"size the pool to at least num_slots * max_blocks + prefix "
            f"budget"))


def quantize_kv_rows(x):
    """Per-row-per-head symmetric int8 quantization of K/V rows — THE
    quantization rule of the int8 block pool (README "Quantized
    serving"); every append path (prefill scatter, chunk write, decode
    append, spec-verify write, multi-tick in-loop append) routes
    through this one function so the grid can never drift between
    sites. ``x [..., Hkv, D]`` → ``(q int8 same shape,
    scale f32 [..., Hkv])`` with ``scale = amax|x| / 127`` per
    (row, head): each row quantizes INDEPENDENTLY — no neighbor, no
    stale pool garbage, no earlier append influences it — which is
    what makes quantized streams deterministic under restore()/replay
    and lets truncate/donate move blocks without touching values.
    All-zero rows carry scale 0 and dequantize to exact zeros."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    q = jnp.clip(jnp.round(xf / jnp.maximum(scale, 1e-30)[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def kv_rows(x):
    """K/V rows ``[..., Hkv, D]`` as the pool stores them, ``[..., Hkv *
    D]`` (``BlockManager``'s docstring): every writer reshapes the rows it
    holds, never the pool."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


FP8_MAX = 448.0   # float8_e4m3fn finite max — the saturation bound


def quantize_kv_rows_fp8(x):
    """THE fp8 write rule, ``quantize_kv_rows``' e4m3 sibling:
    ``x [..., Hkv, D]`` → ``float8_e4m3fn`` same shape via a saturating
    cast (clip to ±448 first: e4m3fn overflow is NaN, not a saturate).
    No scale is computed or written — the pool's per-BLOCK scale planes
    are the constant 1.0 (``BlockManager`` docstring): e4m3's exponent
    IS the per-value scale, and any data-dependent block scale would
    tie a block's bytes to which program first wrote it (decode appends
    cover one row, prefill chunks cover the whole block), breaking
    restore()/replay byte-identity. Rows still quantize independently,
    so every append path shares this one rule exactly like int8's."""
    xf = x.astype(jnp.float32)
    return jnp.clip(xf, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)


def _block_slice(arr, block_id):
    # one block of a pool array, rank-generic: the data [L, nb, bs, KD],
    # int8's per-row planes [L, nb, bs, Hkv] and fp8's per-block planes
    # [L, nb, Hkv] all carry the block on axis 1
    return jax.lax.dynamic_slice(
        arr, (0, block_id) + (0,) * (arr.ndim - 2),
        (arr.shape[0], 1) + arr.shape[2:])


def _block_update(arr, block, block_id):
    return jax.lax.dynamic_update_slice(
        arr, block, (0, block_id) + (0,) * (arr.ndim - 2))


def _prefill_scatter_coords(pool_k, pk, table_row, prompt_len):
    # THE prefill scatter-coordinate rule, shared by the plain and
    # quantized writers (the clamp/drop semantics must not fork):
    # rows [0, prompt_len) map through the slot's block table; rows
    # past prompt_len (bucket padding) map to the sentinel ``nb`` and
    # DROP — they must not land in the pool, where the trailing
    # private block is real but any row beyond it would clip-alias
    # another sequence's block.
    S = pk.shape[1]
    nb, bs = pool_k.shape[1], pool_k.shape[2]
    pos = jnp.arange(S, dtype=jnp.int32)
    bi = jnp.minimum(pos // bs, table_row.shape[0] - 1)
    phys = jnp.where(pos < prompt_len, jnp.take(table_row, bi), nb)
    return phys, pos % bs


def _paged_write_prefill(pool_k, pool_v, pk, pv, table_row, prompt_len):
    # pk/pv: [L, S_pad, Hkv, D] -> scatter through the block table
    # (coordinate rule + padding-drop: _prefill_scatter_coords)
    phys, row = _prefill_scatter_coords(pool_k, pk, table_row,
                                        prompt_len)
    pool_k = pool_k.at[:, phys, row].set(kv_rows(pk), mode="drop")
    pool_v = pool_v.at[:, phys, row].set(kv_rows(pv), mode="drop")
    return pool_k, pool_v


def _paged_write_prefill_q(pool_k, pool_v, pool_ks, pool_vs, pk, pv,
                           table_row, prompt_len):
    # the quantized twin of _paged_write_prefill: the prefill program's
    # full-precision K/V rows quantize ON WRITE (quantize_kv_rows) and
    # land int8 in the pool with their per-row-per-head scales written
    # to the SAME (block, row) coordinates (shared rule:
    # _prefill_scatter_coords) — one drop-mode scatter each, so
    # padding rows vanish from data and scales alike
    phys, row = _prefill_scatter_coords(pool_k, pk, table_row,
                                        prompt_len)
    qk, sk = quantize_kv_rows(pk)
    qv, sv = quantize_kv_rows(pv)
    pool_k = pool_k.at[:, phys, row].set(kv_rows(qk), mode="drop")
    pool_v = pool_v.at[:, phys, row].set(kv_rows(qv), mode="drop")
    pool_ks = pool_ks.at[:, phys, row].set(sk, mode="drop")
    pool_vs = pool_vs.at[:, phys, row].set(sv, mode="drop")
    return pool_k, pool_v, pool_ks, pool_vs


def _paged_write_prefill_f8(pool_k, pool_v, pk, pv, table_row,
                            prompt_len):
    # the fp8 twin: same coordinate rule, but the write is a saturating
    # e4m3 cast of the data alone — the per-block scale planes are the
    # constant 1.0 and are never touched by an append
    # (quantize_kv_rows_fp8 docstring), so only the data scatters
    phys, row = _prefill_scatter_coords(pool_k, pk, table_row,
                                        prompt_len)
    pool_k = pool_k.at[:, phys, row].set(
        kv_rows(quantize_kv_rows_fp8(pk)), mode="drop")
    pool_v = pool_v.at[:, phys, row].set(
        kv_rows(quantize_kv_rows_fp8(pv)), mode="drop")
    return pool_k, pool_v


@functools.lru_cache(maxsize=None)
def _paged_writer(donate, quantized=False, tp=1):
    # donate the POOL arrays (the pool is the cache being updated);
    # the int8 writer donates the scale planes too. ``quantized`` is
    # the pool's kv mode: False (store at pool dtype), "int8"/True
    # (per-row quantize-on-write, scales scatter beside the data) or
    # "fp8" (saturating e4m3 cast, data only — the per-block planes
    # are constant and never written). On a tensor-parallel pool
    # (tp > 1) the writer runs under shard_map with the pool (and the
    # prefill K/V it scatters) partitioned on the head axis — NOT
    # auto-GSPMD: the scatter must hand the pool back with exactly the
    # sharding the sharded step programs expect, or the first
    # post-prefill step pays a re-specialization and the compile-once
    # pin breaks (README "Tensor-parallel serving").
    fp8 = quantized == "fp8"
    int8 = bool(quantized) and not fp8
    impl = (_paged_write_prefill_f8 if fp8
            else _paged_write_prefill_q if int8 else _paged_write_prefill)
    if tp > 1:
        from jax.sharding import PartitionSpec as P
        from .decode import _pool_pspec, _tp_mesh
        # THE pool spec, not a local re-spelling: the scatter must hand
        # the pool back under exactly the sharding the sharded step
        # programs expect (scale planes shard on the same head axis)
        kv = P(None, None, "tp")            # pk/pv [L, S, Hkv, D]
        rep = P()
        if int8:
            pool, sc = _pool_pspec("int8")
            in_specs = (pool, pool, sc, sc, kv, kv, rep, rep)
            out_specs = (pool, pool, sc, sc)
        else:
            # the fp8 writer touches the DATA only, so its spec set is
            # the plain writer's (with the fp8 pool's data spec)
            pool = _pool_pspec("fp8")[0] if fp8 else _pool_pspec(False)
            in_specs = (pool, pool, kv, kv, rep, rep)
            out_specs = (pool, pool)
        impl = jax.shard_map(impl, mesh=_tp_mesh(tp), in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
    if int8:
        return jax.jit(impl, donate_argnums=(0, 1, 2, 3) if donate else ())
    return jax.jit(impl, donate_argnums=(0, 1) if donate else ())


# ------------------------------------------------- tier transfer programs
# The host-RAM spill tier's device side (README "Tiered KV prefix
# cache"): fetch slices one pool block out for the d2h spill, inject
# scatters a readmitted block back. Same compile-once rule as the block
# prefill writers above: the block id is a runtime np.int32 scalar
# (dynamic_slice / dynamic_update_slice), so one trace per (quantized,
# tp[, donate]) serves every block — a python-int index would bake into
# the dispatch-cache key and compile once per block id.

def _tier_fetch_impl(*args):
    # (pool arrays..., block_id): each array's block [L, 1, ...] as a
    # standalone device buffer the host tier copies down (np.asarray is the
    # d2h). On a quantized pool the int8/fp8 data block travels WITH its
    # fp32 scale planes — same block id, no separate bookkeeping
    return tuple(_block_slice(a, args[-1]) for a in args[:-1])


def _tier_inject_impl(*args):
    # readmission, (pool arrays..., their blocks..., block_id): one spilled
    # block's buffers -> pool block ``block_id``
    n = len(args) // 2
    return tuple(_block_update(a, b, args[-1])
                 for a, b in zip(args[:n], args[n:-1]))


_TIER_PROGRAMS = []   # every distinct jitted tier program, for the counter


def _tier_pspecs(quantized, tp):
    # the block buffer [L, 1, bs, Hkv * D] partitions on the SAME head
    # axis as the pool (serving/decode._pool_pspec — THE spec, not a
    # re-spelling), so fetch hands out shards the host gathers and
    # inject hands the pool back exactly as the sharded step programs
    # expect it
    from .decode import _pool_pspec
    if quantized:
        # quantized is the kv mode string here ("int8"/"fp8" — True is
        # accepted as int8): the fp8 pool's per-block planes drop the
        # row axis, so their spec differs from int8's per-row planes
        pool, sc = _pool_pspec("int8" if quantized is True else quantized)
        return (pool, pool, sc, sc), (pool, pool, sc, sc)
    pool = _pool_pspec(False)
    return (pool, pool), (pool, pool)


@functools.lru_cache(maxsize=None)
def _tier_fetch(quantized=False, tp=1):
    # no donation: the spill READS the pool (eviction frees the block's
    # id, not its storage — pool arrays are dense and preallocated)
    impl = _tier_fetch_impl
    if tp > 1:
        from jax.sharding import PartitionSpec as P
        from .decode import _tp_mesh
        pool_specs, block_specs = _tier_pspecs(quantized, tp)
        impl = jax.shard_map(impl, mesh=_tp_mesh(tp),
                             in_specs=pool_specs + (P(),),
                             out_specs=block_specs, check_vma=False)
    fn = jax.jit(impl)
    _TIER_PROGRAMS.append(fn)
    return fn


@functools.lru_cache(maxsize=None)
def _tier_inject(donate, quantized=False, tp=1):
    # donate the POOL arrays (readmission updates the pool in place)
    impl = _tier_inject_impl
    if tp > 1:
        from jax.sharding import PartitionSpec as P
        from .decode import _tp_mesh
        pool_specs, block_specs = _tier_pspecs(quantized, tp)
        impl = jax.shard_map(impl, mesh=_tp_mesh(tp),
                             in_specs=pool_specs + block_specs + (P(),),
                             out_specs=pool_specs, check_vma=False)
    nargs = 4 if quantized else 2
    fn = jax.jit(impl,
                 donate_argnums=tuple(range(nargs)) if donate else ())
    _TIER_PROGRAMS.append(fn)
    return fn


@functools.lru_cache(maxsize=None)
def _state_writer(donate):
    """The jitted slot write of the stores by slot: every array ``[layers,
    num_slots, ...]`` takes its ``[layers, ...]`` at ``slot``."""
    def write(store, held, slot):
        for a, x in zip(store, held):
            if x.shape != a.shape[:1] + a.shape[2:]:
                raise ValueError(
                    f"a slot of the store {a.shape} does not take {x.shape}")
        return tuple(a.at[:, slot].set(x.astype(a.dtype))
                     for a, x in zip(store, held))

    return jax.jit(write, donate_argnums=(0,) if donate else ())


def tier_compilations() -> int:
    """Total traces of the tier transfer programs — the spill/readmit
    half of the bounded-compile contract: stays at one per (geometry,
    quantized, tp, donate) no matter how many blocks spill or readmit,
    and none of them is an engine jit-cache key, so
    ``decode_compilations() == 1`` holds inclusive of readmitted
    chains."""
    return sum(fn._cache_size() for fn in list(_TIER_PROGRAMS))


class PagedKVCache:
    """Block-table KV cache: slot allocator + host tables over a shared
    :class:`~.block_manager.BlockManager` pool — the zero-copy decode
    cache (module docstring). Beside the slot surface (``alloc``/
    ``free``/``num_free``/``lengths``/``write_prefill``/``update``) it
    keeps the tables:

    - ``install_prefix(slot, block_ids)`` — a prefix-cache hit:
      reference the published blocks in the slot's table. No copy; the
      blocks' read pins are the caller's (``PrefixCache.acquire``).
    - ``ensure_capacity(slot, rows)`` — append-block on growth: allocate
      private blocks (each carrying the slot's ownership ref) until the
      table covers ``rows`` logical rows, evicting unpinned trie blocks
      on demand when the pool runs dry.
    - ``free(slot, keep=...)`` — release the table: donated blocks
      (ownership moved to the trie at publish) are unref'd but stay
      allocated; the rest of the private tail is dropped back to the
      heap; shared prefix entries are merely forgotten (their pins are
      released by the engine through ``PrefixCache.release``).

    **Two kinds of cache, one manager.** A token's row in the pool exists
    for the layers that attend over keys and values, and only for them
    (``num_layers`` is THEIR count: a hybrid model's full-attention layers).
    A layer with a recurrent state (``state_geometry``; a Gated DeltaNet
    layer) caches, a sequence, a float32 state and its convolution's last
    inputs: constant in the sequence's length, so it is not paged but
    indexed by SLOT, in :attr:`state` ``(states [layers, num_slots, dk,
    heads * dv] float32, tails [layers, num_slots, conv rows, channels])``.
    (A state's shape is its kernels' to say: a Gated DeltaNet layer's is
    ``kernels.gated_delta_rule.state_shape``, a Mamba-1 layer's ``[d_state,
    d_inner]``, a Mamba-2 block's ``[groups, N, heads / groups * P]``,
    ``kernels.ssd.state_shape``: the state size on the sublanes, the
    channels on the lanes, no minor dimension the device would pad.)
    Nothing here ever zeroes a slot's state: the programs give a span whose
    first position is 0 a zero state, whatever the slot held.
    A layer that attends inside a WINDOW (``window_geometry``) needs its
    last ``window`` keys and values only: constant a slot too, so a third
    kind, :attr:`window` ``(keys, values [layers, num_slots, ring blocks,
    block_size, KD])``: a ring of blocks a slot, a token's row at ``position
    % (ring blocks * block_size)``, which the step programs write and walk
    through a table they compute (``decode.ring_coords``). ``window_geometry``
    is ``(layers, ring blocks)`` where a ring's row is the pool's, or
    ``(layers, ring blocks, key row, value row)`` where the window layers
    have KV heads of their own (MiMo-V2-Flash: 8 against the full layers' 4,
    keys wider than values on both): two stores of different rows under the
    one manager, the ring constant a slot and so admitted, grown and released
    with the slot itself. A layer
    that caches nothing (a cross-decoder's) appears nowhere.
    ``bytes_per_token`` counts the pool, ``state_bytes_per_slot`` and
    ``window_bytes_per_slot`` the two stores.

    The pool's device arrays are the single source of KV truth; the
    decode / suffix-prefill programs update them functionally and the
    engine adopts the result via :meth:`update`. ``num_kv_heads`` x
    ``head_dim`` is the K side's row (and the V side's, unless the pool was
    built with a ``v_dim`` of its own: a latent pool's one row a token has
    no V side, ``BlockManager``). How they are laid out,
    and what :attr:`sentinel` (the block id ``pool.num_blocks`` that fills
    unmapped table entries) means to a writer and to a reader, is stated
    once, in :class:`~.block_manager.BlockManager`'s docstring.
    """

    def __init__(self, num_layers, num_slots, max_seq_len, num_kv_heads,
                 head_dim, dtype=jnp.float32, block_size=32, pool=None,
                 prefix_cache=None, donate=None, kv_dtype=None,
                 state_geometry=None, window_geometry=None):
        from .block_manager import BlockManager
        bs = int(block_size)
        if bs < 1:
            raise ValueError(f"block_size must be >= 1, got {bs}")
        if kv_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"kv_dtype must be None (store at pool dtype), 'int8' or "
                f"'fp8', got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype is not None
        self.fp8 = kv_dtype == "fp8"
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len)
        self.block_size = bs
        self.max_blocks = -(-self.max_seq_len // bs)
        if pool is None:
            pool = BlockManager(num_layers, self.num_slots * self.max_blocks,
                                bs, num_kv_heads, head_dim, dtype=dtype,
                                kv_dtype=kv_dtype)
        if getattr(pool, "kv_dtype", None) != kv_dtype:
            raise ValueError(
                f"pool kv_dtype {getattr(pool, 'kv_dtype', None)!r} does "
                f"not match cache kv_dtype {kv_dtype!r}: a quantized "
                f"cache needs a pool carrying THAT dtype's scale-plane "
                f"layout (int8 per-row vs fp8 per-block, and vice versa)")
        if pool.block_size != bs:
            raise ValueError(
                f"pool block_size {pool.block_size} != cache block_size "
                f"{bs}")
        if pool.num_blocks < self.num_slots * self.max_blocks:
            raise ValueError(
                f"pool of {pool.num_blocks} blocks cannot back "
                f"{self.num_slots} slots x {self.max_blocks} blocks of "
                f"live KV (worst case needs "
                f"{self.num_slots * self.max_blocks})")
        self.pool = pool
        self.prefix_cache = prefix_cache  # evict-on-demand hook (may be None)
        self.sentinel = pool.num_blocks   # out-of-pool id: writes drop
        self.lengths = np.zeros(self.num_slots, np.int32)
        self.tables = np.full((self.num_slots, self.max_blocks),
                              self.sentinel, np.int32)
        self._n_blocks = np.zeros(self.num_slots, np.int32)  # populated
        self._n_shared = np.zeros(self.num_slots, np.int32)  # leading shared
        self._free_heap = list(range(self.num_slots))
        self._free_set = set(self._free_heap)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        # the recurrent layers' store (class docstring): ``state_geometry``
        # is ``(layers, a state's shape, conv rows, channels)``
        self.state = None
        if state_geometry is not None:
            ll, shape, rows, channels = state_geometry
            self.state = (
                jnp.zeros((int(ll), self.num_slots)
                          + tuple(int(n) for n in shape), jnp.float32),
                jnp.zeros((int(ll), self.num_slots, int(rows),
                           int(channels)), dtype))
        # the window layers' store: ``window_geometry`` is ``(layers, ring
        # blocks a slot[, key row, value row])``; a row is the pool's unless
        # the geometry brings its own (class docstring)
        self.window = None
        if window_geometry is not None:
            wl, ring, *rows = (int(n) for n in window_geometry)
            rows = rows or (pool.k.shape[-1], pool.k.shape[-1])
            self.window = tuple(
                jnp.zeros((wl, self.num_slots, ring, bs, w), dtype)
                for w in rows)

    def _bytes_per_slot(self, store) -> int:
        return sum(a.size * np.dtype(a.dtype).itemsize
                   for a in store or ()) // self.num_slots

    @property
    def state_bytes_per_slot(self) -> int:
        """HBM bytes one slot's recurrent state and convolution tail hold
        over all their layers, whatever the sequence's length (0 for a
        model without such layers): the ``serving_state_bytes_per_slot``
        gauge."""
        return self._bytes_per_slot(self.state)

    @property
    def window_bytes_per_slot(self) -> int:
        """HBM bytes one slot's rings of window keys and values hold over
        all the window layers, whatever the sequence's length (0 for a model
        without such layers): the ``serving_window_bytes_per_slot`` gauge."""
        return self._bytes_per_slot(self.window)

    @property
    def store(self):
        """The stores by slot as the step programs take them, one tuple:
        the recurrent layers' pair and then the window layers'."""
        return (self.state or ()) + (self.window or ())

    @store.setter
    def store(self, arrays):
        n = len(self.state or ())
        if n:
            self.state = tuple(arrays[:n])
        if self.window is not None:
            self.window = tuple(arrays[n:])

    def write_state(self, slot, *held):
        """Install what a whole-prompt prefill computed for ``slot``'s
        layers with a store by slot, in :attr:`store`'s order: ``states
        [layers, ...]``, ``tails [layers, conv rows, channels]`` and, for
        window layers, the ring's ``keys, values [layers, ring rows, KD]``
        (one compile-once scatter; the slot is a runtime argument). The
        ring's rows are stored as blocks, ``[ring blocks, bs, KD]``."""
        n = len(self.state or ())
        ring = () if self.window is None else self.window[0].shape[2:4]
        held = tuple(held[:n]) + tuple(
            x.reshape(x.shape[:1] + ring + x.shape[-1:]) for x in held[n:])
        self.store = _state_writer(self._donate)(
            self.store, held, np.int32(slot))

    # ------------------------------------------------------------- slots
    @property
    def num_free(self) -> int:
        return len(self._free_set)

    def alloc(self):
        """Claim a free slot (lowest index first, deterministic)."""
        if not self._free_set:
            return None
        slot = heapq.heappop(self._free_heap)
        self._free_set.discard(slot)
        return slot

    def free(self, slot: int, keep=()):
        """Release a slot's table. ``keep`` is the set of block ids whose
        ownership moved to the prefix trie at publish (donated): they
        lose this slot's pin but stay allocated; every other private
        block drops back to the heap. Shared prefix entries (pinned via
        the trie, not owned here) are forgotten — the engine releases
        those pins separately."""
        if slot in self._free_set:
            raise ValueError(f"slot {slot} double-freed")
        for j in range(int(self._n_shared[slot]), int(self._n_blocks[slot])):
            b = int(self.tables[slot, j])
            if b in keep:
                self.pool.unref(b)   # trie adopted it; give up ownership
            else:
                self.pool.drop(b)    # unref -> 0 -> back to the heap
        self.tables[slot, :] = self.sentinel
        self._n_blocks[slot] = 0
        self._n_shared[slot] = 0
        self.lengths[slot] = 0
        heapq.heappush(self._free_heap, slot)
        self._free_set.add(slot)

    # ------------------------------------------------------------ tables
    def install_prefix(self, slot, block_ids):
        """Zero-copy prefix-hit install: the slot's leading table
        entries REFERENCE the published blocks. The caller holds the
        read pins (``PrefixCache.acquire`` at lookup); nothing is
        dispatched and nothing is copied — this is the whole point."""
        n = len(block_ids)
        if n > self.max_blocks:
            raise ValueError(
                f"prefix of {n} blocks exceeds the {self.max_blocks}-entry "
                f"table")
        for j, b in enumerate(block_ids):
            self.tables[slot, j] = int(b)
        self._n_blocks[slot] = n
        self._n_shared[slot] = n

    def _alloc_block(self):
        b = self.pool.alloc()
        while b is None and self.prefix_cache is not None \
                and self.prefix_cache._evict_one():
            b = self.pool.alloc()
        if b is None:
            # unreachable when the pool is sized num_slots*max_blocks +
            # trie budget (live demand is bounded by the table grid and
            # everything else is an evictable unpinned trie block) —
            # typed so a mis-sized shared pool degrades to
            # preemption-by-recompute (the engine catches it) instead
            # of a server-killing crash
            pool = self.pool
            raise PoolExhausted(
                live_blocks=pool.num_used,
                pinned_blocks=int((pool._ref > 0).sum()),
                free_blocks=pool.num_free)
        self.pool.ref(b)             # the slot's ownership pin
        return b

    def ensure_capacity(self, slot, rows: int):
        """Append private blocks until the slot's table covers ``rows``
        logical rows (decode growth / prefill install). Lazy on purpose:
        unwritten tail blocks stay in the pool for the prefix trie until
        a decode chunk actually needs them."""
        need = min(-(-int(rows) // self.block_size), self.max_blocks)
        n = int(self._n_blocks[slot])
        while n < need:
            self.tables[slot, n] = self._alloc_block()
            n += 1
        self._n_blocks[slot] = n

    def truncate(self, slot, rows: int):
        """Roll the slot's table back to cover exactly ``rows`` logical
        rows: every private tail block past ``ceil(rows / block_size)``
        is dropped (unref-to-zero → back to the free heap — the exact
        inverse of :meth:`ensure_capacity`'s growth, so ``num_free`` is
        restored to what a never-grown slot would show). This is the
        speculative-decode rollback primitive (README "Speculative
        decoding"): a verify span appends draft K/V through the table
        like a prefill chunk, and rejected drafts hand their blocks
        straight back here.

        Shared/donated prefix blocks are NEVER truncated: the keep
        count is clamped at the slot's installed-prefix length, so a
        ``rows`` that would reach into trie-owned blocks only drops the
        private tail (their trie pins — and every other reader's — are
        untouched; the engine releases its own read pins separately at
        retirement). Rows inside kept blocks past ``rows`` hold stale
        K/V, which the attention programs mask by length and the next
        append overwrites — same invariant as a freed slot's rows.

        ``lengths[slot]`` is clamped down to ``rows`` when it exceeds
        it (the engine normally re-sets it to the exact accepted length
        right after). No device work: the pool arrays are untouched.
        """
        keep = max(-(-int(rows) // self.block_size),
                   int(self._n_shared[slot]))
        n = int(self._n_blocks[slot])
        for j in range(keep, n):
            self.pool.drop(int(self.tables[slot, j]))
            self.tables[slot, j] = self.sentinel
        if keep < n:
            self._n_blocks[slot] = keep
        if int(self.lengths[slot]) > int(rows):
            self.lengths[slot] = int(rows)

    def slot_block_ids(self, slot):
        """Physical block ids populating the slot's table, in logical
        order — the donation candidates at retirement."""
        return [int(b) for b in self.tables[slot, :int(self._n_blocks[slot])]]

    def table_fill(self) -> float:
        """Fraction of the [num_slots, max_blocks] table grid populated —
        the ``kv_block_table_fill`` gauge."""
        return float(self._n_blocks.sum()) / float(
            self.num_slots * self.max_blocks)

    def occupancy(self) -> dict:
        """Pool occupancy split for the step-timeline counter tracks
        (``kv_blocks`` on the Chrome trace, README "Cost attribution &
        /debug/profile"): ``live`` = distinct physical blocks some live
        slot table references (shared blocks count once), ``trie`` =
        allocated blocks no live table references (trie-only
        residency), ``free`` = the pool's free heap. Host bookkeeping
        only — deterministic and sync-free."""
        refd = set()
        for slot in range(self.num_slots):
            n = int(self._n_blocks[slot])
            refd.update(int(b) for b in self.tables[slot, :n])
        live = len(refd)
        return {"live": live,
                "trie": max(self.pool.num_used - live, 0),
                "free": self.pool.num_free}

    def slot_kv_bytes(self, slot) -> int:
        """HBM bytes the slot's table currently holds (blocks × block
        bytes, scale planes included on a quantized pool) — the
        ``/debug/requests`` cost column. Dtype-aware by construction:
        the pool's per-block byte counts follow its storage dtype."""
        return int(self._n_blocks[slot]) * (
            self.pool.block_nbytes + self.pool.scale_block_nbytes)

    def used_blocks(self) -> int:
        """Allocated (live + trie) blocks — ONE table scan, shared by
        the byte gauges so a /metrics scrape never pays the
        :meth:`occupancy` walk more than once per series."""
        occ = self.occupancy()
        return occ["live"] + occ["trie"]

    def bytes_per_token(self) -> float:
        """Marginal HBM bytes one cached token costs (block data +
        scale-plane bytes / block_size), the index keys of a pool that has
        them apart (:attr:`index_bytes_per_token`). Pure constants — no
        occupancy scan — so the scrape-time gauge is free."""
        return (self.pool.block_nbytes
                + self.pool.scale_block_nbytes) / self.block_size \
            - self.index_bytes_per_token

    @property
    def index_bytes_per_token(self) -> int:
        """HBM bytes one cached token's INDEX KEYS hold over the layers that
        have an indexer (the V side of a latent pool whose attention is over
        a learned selection; 0 for every other pool): the
        ``serving_index_bytes_per_token`` gauge."""
        v = self.pool.v
        if self.pool.v_layers is None:
            return 0
        return v.shape[0] * v.shape[-1] * np.dtype(v.dtype).itemsize

    def occupancy_bytes(self) -> dict:
        """Pool occupancy in BYTES, split by storage kind — the
        ``kv_pool_bytes{kind="kv|scales"}`` gauges and the
        ``serving_kv_bytes_per_token`` rate (README "Quantized
        serving"). Derived from :meth:`occupancy`'s block accounting ×
        the pool's dtype-aware per-block byte counts, so an int8 pool
        reports int8 bytes plus its fp32 scale planes and the default
        pool reports exactly what it always did with ``scales == 0``.
        ``capacity_*`` cover the whole pool (the fixed HBM budget the
        density bench holds constant); ``used_*`` cover allocated
        (live + trie) blocks; ``per_token`` is the marginal HBM cost
        of one cached token (block bytes / block_size)."""
        used = self.used_blocks()
        kv_b, sc_b = self.pool.block_nbytes, self.pool.scale_block_nbytes
        return {
            "used_kv": used * kv_b,
            "used_scales": used * sc_b,
            "capacity_kv": self.pool.num_blocks * kv_b,
            "capacity_scales": self.pool.num_blocks * sc_b,
            "per_token": self.bytes_per_token(),
            # the recurrent layers' store: by slots, not by blocks
            "used_state": (self.num_slots - self.num_free)
            * self.state_bytes_per_slot,
            "capacity_state": self.num_slots * self.state_bytes_per_slot,
            # the window layers' rings: by slots too
            "used_window": (self.num_slots - self.num_free)
            * self.window_bytes_per_slot,
            "capacity_window": self.num_slots * self.window_bytes_per_slot,
        }

    # ------------------------------------------------------------ writes
    def kv_args(self):
        """The pool arrays as the decode programs take them: plain
        ``(k, v)`` on a full-precision pool, ``((k, k_scale),
        (v, v_scale))`` on an int8 pool — each quantized side is ONE
        pytree argument, so every program signature is unchanged and
        the quantized variant is simply a different trace (keyed apart
        in the engine's jit cache)."""
        p = self.pool
        if self.quantized:
            return (p.k, p.k_scale), (p.v, p.v_scale)
        return p.k, p.v

    def write_prefill(self, slot, pk, pv, prompt_len):
        """Install a prefilled prompt's K/V into ``slot`` — through the
        block table, into private pool blocks (one compile-once scatter
        per prefill bucket; the table row and length are runtime
        arguments). On an int8 pool the full-precision prefill rows
        quantize on write, scales landing beside the data."""
        if pk.shape[1] > self.max_seq_len:
            raise ValueError(
                f"prefill length {pk.shape[1]} exceeds max_seq_len "
                f"{self.max_seq_len}")
        self.ensure_capacity(slot, int(prompt_len))
        p = self.pool
        tp = getattr(p, "tp", 1)
        if self.fp8:
            # data-only write: fp8's per-block scale planes are the
            # constant 1.0 and never touched by appends
            p.k, p.v = _paged_writer(self._donate, "fp8", tp)(
                p.k, p.v, pk, pv,
                jnp.asarray(self.tables[slot]), np.int32(prompt_len))
        elif self.quantized:
            p.k, p.v, p.k_scale, p.v_scale = \
                _paged_writer(self._donate, "int8", tp)(
                    p.k, p.v, p.k_scale, p.v_scale, pk, pv,
                    jnp.asarray(self.tables[slot]), np.int32(prompt_len))
        else:
            p.k, p.v = _paged_writer(self._donate, False, tp)(
                p.k, p.v, pk, pv,
                jnp.asarray(self.tables[slot]), np.int32(prompt_len))
        self.lengths[slot] = int(prompt_len)

    def update(self, new_k, new_v):
        """Adopt the decode/suffix step's functionally-updated pool —
        ``(data, scale)`` pairs on a quantized pool (:meth:`kv_args`'
        inverse), plain arrays otherwise."""
        p = self.pool
        if self.quantized:
            (p.k, p.k_scale), (p.v, p.v_scale) = new_k, new_v
        else:
            p.k, p.v = new_k, new_v
