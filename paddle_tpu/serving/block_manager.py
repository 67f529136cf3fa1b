"""Ref-counted block pool backing the automatic prefix cache.

The pool is the block-granular half of the serving KV story ("Ragged
Paged Attention", PAPERS.md): two dense device arrays (K and V, laid out
as :class:`BlockManager` says) holding KV blocks, plus host-side
bookkeeping — a free-block min-heap (same O(log n) allocator discipline
as :class:`~.kv_cache.PagedKVCache`'s slots) and a per-block reference
count.

Division of labor: this class owns *physical* blocks (allocation,
refcounts, storage); :class:`~.prefix_cache.PrefixCache` owns *logical*
identity (the hash-trie from token content to block id, LRU eviction
order, hit/miss accounting). The pool IS the KV cache
(:class:`~.kv_cache.PagedKVCache`) — live sequences reference blocks through
per-slot block tables, published blocks are shared zero-copy (one
block, N refs), and divergence is safe because writes only ever land
in blocks the writing sequence privately owns (the COW fork: a table
is shared-prefix + private-tail, and the tail is allocated fresh, never
forked in place).

Ownership discipline for table-referenced blocks: every block in a
live table holds >= 1 ref — shared prefix blocks are pinned via
:meth:`PrefixCache.acquire`, private tail blocks carry the owning
sequence's pin from :meth:`alloc` + :meth:`ref`. :meth:`drop` releases
one pin and returns the block to the free heap exactly when the count
hits zero, so a mid-decode cancel frees the private tail while the
shared prefix (still pinned by the trie's other readers) survives.
"""
from __future__ import annotations

import heapq

import jax
import jax.numpy as jnp
import numpy as np


class StagingPool:
    """Reusable pageable host buffers for tier spills, one free list
    per (shape, dtype).

    Every spill used to land in freshly-allocated numpy per block, so a
    long-running tiered engine paid an allocator round-trip (and a page
    fault on first touch) per spilled block forever. A spill's staging
    need is EXACTLY the pool's per-block shapes — a handful of keys —
    so the steady state is one buffer per shape in flight:
    :meth:`take` pops a free buffer or allocates the shape's first,
    recycling (tier drop / readmission) gives it back, and
    ``allocations`` counts real ``np.empty`` calls per shape — the
    regression pin is one per shape, not one per spill."""

    def __init__(self):
        self._free = {}          # (plane, shape, dtype str) -> [buffers]
        #: (plane, shape, dtype str) -> np.empty count (the test pin)
        self.allocations = {}

    @staticmethod
    def _key(plane, shape, dtype):
        return (plane, tuple(int(s) for s in shape), np.dtype(dtype).str)

    def take(self, plane, shape, dtype):
        """A writable host buffer for the named plane (``k`` / ``v`` /
        scale) of the shape, reused when one is free. The plane name
        joins the key so the k and v planes — same shape — each own
        exactly one steady-state buffer instead of contending for one
        free list."""
        key = self._key(plane, shape, dtype)
        free = self._free.get(key)
        if free:
            return free.pop()
        self.allocations[key] = self.allocations.get(key, 0) + 1
        return np.empty(key[1], np.dtype(dtype))

    def give(self, bufs):
        """Return a spill's buffers (a ``read_block``-shaped dict) to
        the free lists. Only call once NOTHING can read them again —
        an alias held by a tier entry or an in-flight h2d would read
        the next spill's bytes."""
        for plane, b in bufs.items():
            self._free.setdefault(self._key(plane, b.shape, b.dtype),
                                  []).append(b)


class BlockManager:
    """Physical block pool: device arrays + free heap + refcounts.

    **The stored layout** (this is its one statement; ``pool_shape``
    its one definition): ``k`` and ``v`` are ``[L, num_blocks,
    block_size, Hkv * D]``, ``L`` the layers that attend over cached keys
    and values (every layer of most models; a hybrid model's
    full-attention layers only: a layer with a recurrent state has no row
    here, its cache is ``PagedKVCache.state``, by slot), a row's heads
    side by side on the minor,
    lane-dense axis, which is how the ragged attention kernel reads a
    block: it is handed the whole buffer and fetches block ``(layer,
    table entry)`` from where it lies, so no step program slices,
    copies or re-lays-out a layer of the pool. The logical ``(Hkv, D)``
    are ``num_kv_heads`` / ``head_dim``; code that wants them apart
    reshapes the few rows or the block it holds, never the pool.
    Writers scatter rows at ``[layer, block, row]``
    (``serving.decode._kv_write``), in place on the donated buffer.
    Under tensor parallelism the minor axis is cut into ``tp``
    contiguous pieces, ``Hkv / tp`` whole heads each.

    **A latent pool** (``v_dim=0``; multi-head latent attention): a token's
    row is not heads of K and V but ONE row, the normalised latent and the
    rotated shared key padded to whole lanes (``num_kv_heads`` 1,
    ``head_dim`` that width: 512 + 64 -> 640 for DeepSeek-V2), stored once,
    on the K side; the V side has width 0, so every program signature,
    writer and lifecycle move carries it unchanged and for nothing. The
    kernel that reads it is ``kernels.pallas_mla_ragged_attention``.
    Where that attention is over a learned selection (``kernels.dsa``) the V
    side is the SECOND per-token cache, the index keys: ``v_dim`` their width
    and ``v_layers`` the layers that have an indexer, ``[v_layers,
    num_blocks, block_size, v_dim]``, addressed by the same block ids, so a
    block is both rows in every lifecycle move.

    **The sentinel rule**: the block id ``num_blocks`` (one past the
    last block; ``PagedKVCache.sentinel``) marks an unmapped table
    entry and the target of a write that must not happen (a dead packed
    row, a padding row, a position past the table). Writes index the
    pool by ``(layer, block, row)`` in drop mode, so a sentinel write is
    out of range on the block axis and vanishes; reads clamp the table
    entry into ``[0, num_blocks)`` BEFORE the layer is applied and mask
    the rows by length. Never flatten ``(layer, block)`` to ``layer *
    num_blocks + block`` around a sentinel: that is block 0 of the next
    layer.

    ``kv_dtype="int8"`` stores the pool block-quantized (README
    "Quantized serving"): ``k``/``v`` become int8 and each block
    carries a per-row-per-head fp32 SCALE PLANE alongside it —
    ``k_scale``/``v_scale`` ``[L, num_blocks, block_size, Hkv]``,
    indexed by the SAME physical block id as the data, so every
    lifecycle move (alloc/free/ref/drop, trie donation, speculative
    truncation) carries a block's scales with it for free: there is no
    separate scale bookkeeping to drift. Appends quantize on the way
    in (``serving/decode.quantize_kv_rows``); the attention kernels
    dequantize right after the table-indirect DMA, so HBM block bytes
    are int8 (a ~4x cut vs fp32 at head_dim 64; scales cost
    ``4 / head_dim`` of the int8 data) while the matmuls stay
    full-precision.

    ``kv_dtype="fp8"`` stores ``float8_e4m3fn`` with PER-BLOCK
    per-head scale planes ``[L, num_blocks, Hkv]`` — ``block_size``×
    fewer scale bytes than int8's per-row planes. The block scale is
    the constant 1.0 by construction: e4m3's own exponent is the
    per-value scale, and a data-dependent block scale would make a
    block's bytes depend on WHICH program first wrote it (a decode
    append covers one row, a prefill chunk covers the whole block), so
    restore()-by-recompute could not replay byte-identically. The
    planes still ride the same physical block id through every
    lifecycle move and the kernels still apply them post-dot — the
    structural (data, scale) plumbing is identical to int8's, only the
    write rule differs (``kv_cache.quantize_kv_rows_fp8``: saturating
    cast, no scale write)."""

    def __init__(self, num_layers, num_blocks, block_size, num_kv_heads,
                 head_dim, dtype=jnp.float32, kv_dtype=None, mesh=None,
                 v_dim=None, v_layers=None):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if kv_dtype not in (None, "int8", "fp8"):
            raise ValueError(
                f"kv_dtype must be None (store at pool dtype), 'int8' or "
                f"'fp8', got {kv_dtype!r}")
        if v_dim is not None and (kv_dtype is not None or mesh is not None):
            raise ValueError(
                "a pool whose V side differs from its K side (a latent "
                "pool) is full-precision and lives on one chip")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype is not None
        self.fp8 = kv_dtype == "fp8"
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        shape = self.pool_shape(num_layers, self.num_blocks,
                                self.block_size, num_kv_heads, head_dim)
        store = (jnp.float8_e4m3fn if self.fp8
                 else jnp.int8 if self.quantized else dtype)
        self.k = jnp.zeros(shape, store)
        #: layers of the V side where it is a second per-token cache of its
        #: own (the index keys), else None
        self.v_layers = None if v_layers is None else int(v_layers)
        self.v = jnp.zeros(
            shape if v_dim is None
            else (int(num_layers if v_layers is None else v_layers),)
            + shape[1:-1] + (int(v_dim),), store)
        if self.fp8:
            # per-BLOCK planes, constant 1.0 (class docstring): never
            # rewritten by appends, only read by the kernels' post-dot
            # rescale — initialized to ones so a fresh block
            # dequantizes as identity
            sshape = (num_layers, self.num_blocks, num_kv_heads)
            self.k_scale = jnp.ones(sshape, jnp.float32)
            self.v_scale = jnp.ones(sshape, jnp.float32)
        elif self.quantized:
            sshape = shape[:-1] + (num_kv_heads,)
            self.k_scale = jnp.zeros(sshape, jnp.float32)
            self.v_scale = jnp.zeros(sshape, jnp.float32)
        else:
            self.k_scale = self.v_scale = None
        # tensor-parallel pool partition (README "Tensor-parallel
        # serving"): commit the arrays head-sharded over the ("tp",)
        # mesh — each shard owns Hkv/tp heads of EVERY physical block,
        # scale planes on the same axis, so ALL the host bookkeeping
        # below (heap, refcounts, tables) stays replicated-by-identity
        # and every lifecycle move carries the shards for free.
        self.tp = 1
        if mesh is not None:
            from jax.sharding import NamedSharding
            from .decode import _pool_pspec
            self.tp = mesh.devices.size
            if num_kv_heads % self.tp:
                raise ValueError(
                    f"pool of {num_kv_heads} KV heads cannot partition "
                    f"over a {self.tp}-device mesh")
            # THE pool spec (serving/decode._pool_pspec), not a local
            # re-spelling: a spelling difference here would read as a
            # fresh sharding to the pjit cache every step
            if self.quantized:
                data_spec, scale_spec = _pool_pspec(self.kv_dtype)
                scale_s = NamedSharding(mesh, scale_spec)
                self.k_scale = jax.device_put(self.k_scale, scale_s)
                self.v_scale = jax.device_put(self.v_scale, scale_s)
            else:
                data_spec = _pool_pspec(False)
            data_s = NamedSharding(mesh, data_spec)
            self.k = jax.device_put(self.k, data_s)
            self.v = jax.device_put(self.v, data_s)
        self._free_heap = list(range(self.num_blocks))
        self._free_set = set(self._free_heap)
        self._ref = np.zeros(self.num_blocks, np.int32)
        # spill staging (README "Tiered KV prefix cache"): per-shape
        # reusable host buffers for read_block copies, recycled by the
        # host tier's drop/readmit paths through recycle_staging
        self.staging = StagingPool()

    @staticmethod
    def pool_shape(num_layers, num_blocks, block_size, num_kv_heads,
                   head_dim):
        """The stored shape of ``k`` and of ``v`` (class docstring)."""
        return (int(num_layers), int(num_blocks), int(block_size),
                int(num_kv_heads) * int(head_dim))

    # ---------------------------------------------------------- allocator
    @property
    def num_free(self) -> int:
        return len(self._free_set)

    @property
    def num_used(self) -> int:
        """Live blocks (published + pinned) — the ``kv_prefix_blocks``
        gauge on ``/metrics``."""
        return self.num_blocks - self.num_free

    @property
    def num_shared(self) -> int:
        """Blocks with refcount >= 2 (physically shared by concurrent
        readers) — the ``kv_blocks_shared`` gauge on ``/metrics``."""
        return int((self._ref >= 2).sum())

    @property
    def block_nbytes(self) -> int:
        """HBM bytes one block's K/V DATA holds across all layers — the
        unit of the ``/debug/requests`` per-request KV-bytes column and
        the cost observatory's occupancy-to-bytes conversion. Abstract
        (shape × itemsize): no device sync. Dtype-aware by
        construction: an int8 pool reports int8 bytes (scale planes are
        accounted separately, :attr:`scale_block_nbytes`)."""
        per = (self.k.size + self.v.size) * np.dtype(self.k.dtype).itemsize
        return per // self.num_blocks

    @property
    def scale_block_nbytes(self) -> int:
        """HBM bytes one block's SCALE PLANES hold across all layers,
        K and V (0 on an unquantized pool) — the ``kind="scales"``
        half of the ``kv_pool_bytes`` gauge."""
        if not self.quantized:
            return 0
        per = self.k_scale.size * np.dtype(self.k_scale.dtype).itemsize
        return 2 * per // self.num_blocks

    def alloc(self):
        """Claim a free block (lowest id first, deterministic); None when
        the pool is exhausted (the caller evicts or skips publishing)."""
        if not self._free_set:
            return None
        block = heapq.heappop(self._free_heap)
        self._free_set.discard(block)
        return block

    def free(self, block: int):
        if block in self._free_set:
            raise ValueError(f"block {block} double-freed")
        if self._ref[block]:
            raise ValueError(
                f"block {block} freed with refcount {int(self._ref[block])}")
        heapq.heappush(self._free_heap, block)
        self._free_set.add(block)

    # ---------------------------------------------------------- refcounts
    def ref(self, block: int):
        """Pin a block (a sequence's admission matched it)."""
        self._ref[block] += 1

    def unref(self, block: int) -> int:
        """Release one pin; returns the remaining count."""
        if self._ref[block] <= 0:
            raise ValueError(f"block {block} unref'd below zero")
        self._ref[block] -= 1
        return int(self._ref[block])

    def refcount(self, block: int) -> int:
        return int(self._ref[block])

    # --------------------------------------------------- tier transfers
    def read_block(self, block: int) -> dict:
        """Copy one block device→host for the spill tier: numpy buffers
        keyed like the pool's own arrays (``k``/``v`` and, on an int8
        pool, ``k_scale``/``v_scale`` — the scale planes ride the same
        block id, README "Quantized serving"). One jitted fetch program
        per (quantized, tp) — the block id is a runtime scalar
        (``kv_cache._tier_fetch``), so spilling never adds a trace."""
        from .kv_cache import _tier_fetch
        bid = np.int32(block)
        if self.quantized:
            # kv_dtype keys the program: the fp8 pool's per-BLOCK scale
            # planes are a different rank (and TP spec) than int8's
            # per-row planes
            bk, bv, bks, bvs = _tier_fetch(self.kv_dtype, self.tp)(
                self.k, self.v, self.k_scale, self.v_scale, bid)
            return self._stage(k=bk, v=bv, k_scale=bks, v_scale=bvs)
        bk, bv = _tier_fetch(False, self.tp)(self.k, self.v, bid)
        return self._stage(k=bk, v=bv)

    def _stage(self, **arrays):
        """Land the fetched block in staging-pool buffers (one real
        allocation per shape over the pool's lifetime, not per spill):
        ``np.asarray`` on the device result may be a zero-copy view of
        the device buffer, so the copy into the reusable buffer is also
        what unpins the spill bytes from XLA-owned memory."""
        out = {}
        for name, arr in arrays.items():
            host = np.asarray(arr)
            buf = self.staging.take(name, host.shape, host.dtype)
            np.copyto(buf, host)
            out[name] = buf
        return out

    def recycle_staging(self, bufs):
        """Hand a spill's staging buffers back for reuse once their
        tier entry is dead (dropped, replaced, or readmitted and
        injected). The sync makes the readmission case safe: the
        injection program may still be reading the host buffers under
        async dispatch, and a recycled buffer's next spill would race
        it — waiting on the pool arrays (the injection's outputs)
        fences every pending read."""
        jax.block_until_ready(self.k)
        jax.block_until_ready(self.v)
        if self.quantized:
            jax.block_until_ready(self.k_scale)
            jax.block_until_ready(self.v_scale)
        self.staging.give(bufs)

    def write_block(self, block: int, bufs: dict):
        """Stream one spilled block's host buffers back h2d into pool
        block ``block`` (readmission). Donates the pool arrays off-CPU —
        an in-place scatter, same discipline as the paged prefill
        writer; on a tensor-parallel pool the program runs under
        shard_map so the pool comes back exactly as the sharded step
        programs expect it."""
        from .kv_cache import _tier_inject
        donate = jax.default_backend() != "cpu"
        bid = np.int32(block)
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = _tier_inject(
                donate, self.kv_dtype, self.tp)(
                    self.k, self.v, self.k_scale, self.v_scale,
                    jnp.asarray(bufs["k"]), jnp.asarray(bufs["v"]),
                    jnp.asarray(bufs["k_scale"]),
                    jnp.asarray(bufs["v_scale"]), bid)
        else:
            self.k, self.v = _tier_inject(donate, False, self.tp)(
                self.k, self.v, jnp.asarray(bufs["k"]),
                jnp.asarray(bufs["v"]), bid)

    def drop(self, block: int) -> bool:
        """Release one pin and return the block to the free heap iff the
        count hit zero. The paged cache's private-tail release: the heap
        gets the block back EXACTLY once (a second drop raises through
        :meth:`unref`'s below-zero guard), and a block still pinned by
        other readers — a donated prefix block with live hits — merely
        loses this reader. Returns whether the block was freed."""
        if self.unref(block) == 0:
            self.free(block)
            return True
        return False
