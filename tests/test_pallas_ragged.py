"""Unified ragged prefill+decode paged attention kernel parity
(kernels/pallas_ragged_attention.py, "Ragged Paged Attention",
PAPERS.md): a PACKED buffer of variable-length query spans — decode
rows (span 1) and prefill chunks (span n) — attends causally through
per-sequence block tables in ONE kernel invocation. Interpret-mode
oracle suite mirroring test_pallas_paged_decode.py, plus the properties
the unification itself must pin:

- a span-1 row is the single-query paged decode kernel's within float32
  rounding (pallas vs pallas: the ragged kernel sums a KV head's products
  on their own, the decode kernel over a block-diagonal wide row; the same
  mathematics) and BITWISE reference vs reference;
- the per-head walk over the head-major query at the cells' head counts
  (32 / 8, 16 / 16, 30 / 30) and at groups of 2, 3 and 8, spans crossing
  query blocks beside one-token spans;
- the walk in groups of pages: spans and causal diagonals that end inside a
  group, a last group that reaches past ``kvlen`` into a NaN-poisoned pool,
  and the one-token walk of a decode row against the general walk on the
  same rows (``tests/test_pallas_ragged_groups.py``); quantized planes
  carried through a group (``tests/test_pallas_ragged_quantized.py``): files
  of their own, every case being a program of its own to lower, so that no
  file is the floor under the suite's wall (ROADMAP D6);
- sentinel tables / dead rows / packed padding stay finite and come
  back as exact zeros;
- the kernel's iteration space: its work list of (query block, row)
  pairs and each pair's KV walk equal a token-by-token enumeration of
  the masks, and the shapes that walk exercises (sparse decode tables,
  a causal cut that differs per query block, holes in the packed
  buffer, an int8 pool) match the oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import pallas_paged_decode, pallas_ragged_attention
from paddle_tpu.kernels.pallas_ragged_attention import (_query_block,
                                                        _work_list)
from serving_support import compiled_once as _compiled_once
from test_one_timeline import GRID_CASES, _live_pairs


ragged_paged_attention_pallas = _compiled_once(
    pallas_ragged_attention.ragged_paged_attention_pallas)
ragged_attention_reference = _compiled_once(
    pallas_ragged_attention.ragged_attention_reference)
paged_decode_attention_pallas = _compiled_once(
    pallas_paged_decode.paged_decode_attention_pallas)
paged_decode_attention_reference = _compiled_once(
    pallas_paged_decode.paged_decode_attention_reference)


def _mk(R, spans, H, Hkv, D, mb, bs, seed=0, dtype=jnp.float32, T=None):
    """Pool + scrambled tables + packed spans. ``spans``: per-sequence
    (qlen, kvlen); qlen=0 rows are dead. Returns the kernel's full
    argument tuple; T pads the packed buffer past the spans (dead
    packed rows)."""
    r = np.random.RandomState(seed)
    num_blocks = R * mb + 2
    pool_k = jnp.asarray(r.randn(num_blocks, bs, Hkv, D), dtype)
    pool_v = jnp.asarray(r.randn(num_blocks, bs, Hkv, D), dtype)
    perm = r.permutation(R * mb)
    tables = np.asarray(perm.reshape(R, mb), np.int32)
    qstart = np.zeros(R, np.int32)
    qlen = np.zeros(R, np.int32)
    kvlen = np.zeros(R, np.int32)
    cur = 0
    for i, (ql, kl) in enumerate(spans):
        qstart[i], qlen[i], kvlen[i] = cur, ql, kl
        cur += ql
    T = T or cur
    q = jnp.asarray(r.randn(T, H, D), dtype)
    return (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(qstart),
            jnp.asarray(qlen), jnp.asarray(kvlen))


MIXED = [(1, 40), (5, 37), (1, 3), (16, 16), (0, 0), (9, 64)]


class TestRaggedKernelParity:
    @pytest.mark.parametrize("H,Hkv,D,mb,bs", [
        (8, 2, 64, 4, 32),        # GQA group 4
        (8, 1, 64, 3, 16),        # MQA, small blocks
        (4, 4, 64, 4, 16),        # MHA
        (32, 2, 32, 4, 16),       # GQA group 16 (Nemotron-3-Nano's 32 on 2)
        (20, 1, 128, 4, 16),      # MQA group 20, not a power of two, D 128
                                  # (Jamba2-3B's 20 on 1): decode rows, chunks
    ])
    def test_matches_reference_mixed_spans(self, H, Hkv, D, mb, bs):
        """Decode rows, multi-token chunks (1..block and beyond), a
        dead row — one invocation, all spans match the oracle."""
        spans = [(1, mb * bs), (min(5, bs), 12), (0, 0), (bs, bs),
                 (3, 2 * bs + 3), (1, 1)]
        args = _mk(len(spans), spans, H, Hkv, D, mb, bs, seed=H + bs)
        got = ragged_paged_attention_pallas(*args)
        want = ragged_attention_reference(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("H,Hkv", [(8, 2), (16, 4), (32, 8)])
    def test_span1_vs_paged_decode_kernel(self, H, Hkv):
        """A span-1 row IS the old single-query kernel's row: reference vs
        reference bitwise; pallas vs pallas the same block walk and the same
        online-softmax mathematics, a KV head's sums taken on their own
        (the decode kernel sums over a block-diagonal wide row), so float32
        rounding is the bound at one page an update and at the default (the
        whole 4-entry table an update) alike."""
        spans = [(1, 40), (1, 7), (1, 64)]
        q, pk, pv, tbl, qs, ql, kl = _mk(3, spans, H, Hkv, 64, 4, 16,
                                         seed=3)
        got_r = np.asarray(ragged_attention_reference(
            q, pk, pv, tbl, qs, ql, kl))
        # the packed buffer in span order == one query per sequence
        old_k = np.asarray(paged_decode_attention_pallas(
            q, pk, pv, tbl, kl))
        old_r = np.asarray(paged_decode_attention_reference(
            q, pk, pv, tbl, kl))
        assert (got_r == old_r).all()
        np.testing.assert_allclose(
            np.asarray(ragged_paged_attention_pallas(
                q, pk, pv, tbl, qs, ql, kl, pages=1)), old_k, rtol=2e-5,
            atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(ragged_paged_attention_pallas(
                q, pk, pv, tbl, qs, ql, kl)), old_k, rtol=2e-5, atol=2e-5)

    def test_sentinel_dead_rows_and_padding_zero_and_finite(self):
        """Sentinel table tails clamp harmlessly; a dead row (qlen 0)
        and packed rows past every span come back as EXACT zeros from
        kernel and oracle alike — the engine's padded token buffer
        must never leak NaN into the residual stream."""
        spans = [(1, 20), (4, 17), (0, 0)]
        q, pk, pv, tbl, qs, ql, kl = _mk(3, spans, 8, 4, 16, 4, 8,
                                         seed=11, T=12)
        tbl = np.asarray(tbl).copy()
        nb = pk.shape[0]
        tbl[1, 3:] = nb                   # unmapped tail -> sentinel
        tbl[2, :] = nb                    # dead row: all-sentinel
        tbl = jnp.asarray(tbl)
        got = np.asarray(ragged_paged_attention_pallas(
            q, pk, pv, tbl, qs, ql, kl))
        ref = np.asarray(ragged_attention_reference(
            q, pk, pv, tbl, qs, ql, kl))
        assert np.isfinite(got).all() and np.isfinite(ref).all()
        assert (got[5:] == 0).all()       # rows past the spans
        assert (ref[5:] == 0).all()
        np.testing.assert_allclose(got[:5], ref[:5], rtol=2e-5,
                                   atol=2e-5)

    def test_bf16_io(self):
        spans = [(1, 30), (6, 22), (2, 8)]
        args = _mk(3, spans, 8, 8, 128, 2, 16, seed=13,
                   dtype=jnp.bfloat16)
        got = ragged_paged_attention_pallas(*args)
        want = ragged_attention_reference(*args)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=3e-2, atol=3e-2)

    def test_jit_and_scan_composable(self):
        """Must trace under jit inside a lax.scan over layers — the
        exact shape of the unified serving step's layer loop (the stored
        pool carried whole, the layer's index scanned, one shared table +
        span metadata)."""
        R, H, Hkv, D, mb, bs, L = 2, 4, 2, 64, 4, 16, 3
        r = np.random.RandomState(5)
        T = 6
        q = jnp.asarray(r.randn(L, T, H, D), jnp.float32)
        num_blocks = R * mb
        pk = jnp.asarray(r.randn(L, num_blocks, bs, Hkv * D), jnp.float32)
        pv = jnp.asarray(r.randn(L, num_blocks, bs, Hkv * D), jnp.float32)
        tbl = jnp.asarray(
            r.permutation(num_blocks).reshape(R, mb), jnp.int32)
        qs = jnp.asarray([0, 1], jnp.int32)
        ql = jnp.asarray([1, 5], jnp.int32)
        kl = jnp.asarray([40, 37], jnp.int32)

        @jax.jit
        def run(q, pk, pv):
            def body(pool, xs):
                qq, layer = xs
                return pool, ragged_paged_attention_pallas(
                    qq, *pool, tbl, qs, ql, kl, layer=layer)
            _, outs = jax.lax.scan(body, (pk, pv),
                                   (q, jnp.arange(L, dtype=jnp.int32)))
            return outs

        outs = np.asarray(run(q, pk, pv))
        for layer in range(L):
            want = np.asarray(ragged_attention_reference(
                q[layer], pk[layer].reshape(num_blocks, bs, Hkv, D),
                pv[layer].reshape(num_blocks, bs, Hkv, D), tbl, qs, ql, kl))
            np.testing.assert_allclose(outs[layer], want, rtol=2e-5,
                                       atol=2e-5)

    def test_query_block_tiling_invariant(self):
        """Packed buffers larger than one query block (the kernel's
        block_q grid dim) still match — spans crossing a query-block
        boundary are handled by the masked read-modify-write."""
        spans = [(1, 33), (40, 40), (1, 60), (25, 26)]
        args = _mk(4, spans, 8, 2, 64, 4, 16, seed=17)
        got = ragged_paged_attention_pallas(*args, block_q=64)
        want = ragged_attention_reference(*args)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------------- the kernel's work list
WORK_CASES = dict(GRID_CASES)
WORK_CASES.update({
    # (qstart, qlen, kvlen) over 40 packed tokens, 8 table entries of 16
    "one_span_whole_buffer": ([0, 0, 0, 0], [40, 0, 0, 0], [128, 0, 0, 0]),
    "spans_of_1_inside_block_0": ([0, 1, 2, 3], [1, 1, 1, 1],
                                  [1, 16, 17, 128]),
    "span_from_mid_block_to_mid_block": ([0, 3, 0, 0], [0, 19, 0, 0],
                                         [0, 19, 0, 0]),
    # a dead row whose stale qstart / kvlen point inside a live block
    "stale_dead_row_inside_live_block": ([2, 4, 0, 30], [9, 0, 2, 0],
                                         [77, 128, 2, 64]),
    "rows_out_of_packed_order": ([21, 0, 20, 5], [19, 5, 1, 15],
                                 [19, 128, 100, 47]),
})


@pytest.mark.parametrize("case", sorted(WORK_CASES))
@pytest.mark.parametrize("heads,block_q", [(4, 16), (32, 256), (3, 8)])
def test_work_list_equals_enumeration(case, heads, block_q):
    """``_work_list`` (jnp, inside the program) against the pairs found by
    walking every token: ordered by query block then row, every query block
    at least once, the first visit of each flagged, the tail dead and on
    the last block, and each pair's KV walk ending at the row's own length
    and the causal diagonal."""
    qstart, qlen, kvlen = WORK_CASES[case]
    geometry = dict(heads=heads, block_q=block_q, block_size=16,
                    table_entries=8, packed_tokens=40)
    pairs, nq = _live_pairs(qstart, qlen, kvlen, **geometry)
    want = []
    for qi in range(nq):
        here = [(qi, r, n) for (b, r), n in sorted(pairs.items()) if b == qi]
        want += here or [(qi, None, 0)]
    R = len(qstart)
    assert len(want) <= nq + R - 1 or not any(qlen)
    bq = _query_block(block_q, heads, 40)
    wq, wr, first, wn = (np.asarray(a) for a in jax.jit(
        lambda a, b, c: _work_list(
            a, b, c, nq=nq, tokens_per_block=bq // heads, block_size=16,
            table_entries=8))(*(jnp.asarray(x, jnp.int32)
                                for x in (qstart, qlen, kvlen))))
    assert wq.shape == (nq + R,)
    for j, (qi, r, n) in enumerate(want):
        assert (wq[j], wn[j]) == (qi, n), (j, want[j])
        assert r is None or wr[j] == r
        assert first[j] == (j == 0 or want[j - 1][0] != qi)
    tail = slice(len(want), None)
    assert (wq[tail] == nq - 1).all() and not wn[tail].any()
    assert not first[tail].any() and (wr[tail] < R).all()


def _poison_stale_rows(pool, tables, kvlen, qlen):
    """NaN wherever no live row may read: unmapped blocks and the rows of
    a mapped block past its sequence's length."""
    pool = np.asarray(pool).copy()
    nb, bs = pool.shape[:2]
    live = np.zeros((nb, bs), bool)
    for r, kl in enumerate(np.asarray(kvlen)):
        for b in range(-(-int(kl) // bs) if int(qlen[r]) else 0):
            live[np.asarray(tables)[r, b], :min(bs, int(kl) - b * bs)] = True
    pool[~live] = np.nan
    return jnp.asarray(pool)


WALK_CASES = {
    # name: (spans, H, Hkv, D, mb, bs, block_q)
    # decode rows whose 64-entry tables are sentinel past a few blocks
    "decode_only_sparse_tables": (
        [(1, 40), (1, 1), (1, 97), (0, 0), (1, 16), (1, 33)],
        8, 2, 64, 64, 16, 256),
    # kvlen several times qlen over five query blocks of 8 tokens: the
    # causal diagonal cuts each block's walk at another KV block
    "chunk_resumed_far_into_its_prompt": (
        [(1, 90), (40, 200), (1, 7)], 8, 2, 64, 16, 16, 64),
    # a first chunk: the diagonal crosses every query block
    "first_chunk_kvlen_equals_qlen": (
        [(48, 48), (1, 130)], 8, 4, 32, 12, 16, 64),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_kv_walk_matches_reference(case):
    spans, H, Hkv, D, mb, bs, block_q = WALK_CASES[case]
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, H, Hkv, D, mb, bs,
                                     seed=len(case))
    tbl = np.asarray(tbl).copy()
    for r, (_, kvlen) in enumerate(spans):
        tbl[r, -(-kvlen // bs):] = pk.shape[0]      # unmapped -> sentinel
    tbl = jnp.asarray(tbl)
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    got = ragged_paged_attention_pallas(q, pk, pv, tbl, qs, ql, kl,
                                        block_q=block_q)
    want = ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("spans", [
    [(3, 20), (5, 17), (0, 0)],         # holes between spans and a tail
    [(0, 0), (0, 0), (0, 0)],           # every row dead
], ids=["holes_and_tail", "all_dead"])
def test_rows_in_no_span_are_exact_zeros(spans):
    """Packed rows past the last span AND between spans (the spans need
    not touch) come back exactly 0 and finite, stale pool rows being
    NaN."""
    q, pk, pv, tbl, qs, ql, kl = _mk(3, spans, 8, 4, 16, 4, 8, seed=23,
                                     T=30)
    qs = jnp.asarray([2, 11, 19], jnp.int32)        # gaps: 0-1, 5-10, 16-
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=32))
    ref = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl))
    in_span = np.zeros(30, bool)
    for s, n in zip(np.asarray(qs), np.asarray(ql)):
        in_span[int(s):int(s) + int(n)] = True
    assert np.isfinite(got).all()
    assert (got[~in_span] == 0).all() and (ref[~in_span] == 0).all()
    np.testing.assert_allclose(got[in_span], ref[in_span], rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("nh,nkv", [(12, 12), (12, 4), (30, 30)])
def test_ragged_attention_pads_no_head_count(nh, nkv, monkeypatch):
    """30 heads are the first count in the benchmark that is no multiple of
    8: the heads are a LEADING dimension of the kernel's head-major query
    ``[Hkv, T * G, D]``, so 12 or 30 of them need no padded row (the call
    sees exactly ``nkv`` planes of ``T * G`` rows and returns as many), and
    the result is the oracle's, MHA and GQA alike."""
    from paddle_tpu.kernels import pallas_ragged_attention as pra
    assert not hasattr(pra, "wide_rows")
    assert not hasattr(pra, "_ragged_padded_heads")
    seen = []
    real = pra._ragged_call

    def call(q_hm, *a, **kw):
        out = real(q_hm, *a, **kw)
        seen.append((q_hm.shape, out.shape))
        return out
    monkeypatch.setattr(pra, "_ragged_call", call)
    rng = np.random.RandomState(nh + nkv)
    hd, bs, nb, mb = 16, 8, 24, 6
    rows = [(1, 20), (9, 30), (0, 0), (1, 1)]
    qlen = np.array([q for q, _ in rows], np.int32)
    kvlen = np.array([k for _, k in rows], np.int32)
    qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).astype(np.int32)
    tables = np.full((len(rows), mb), nb, np.int32)
    perm, used = rng.permutation(nb), 0
    for r, n in enumerate(kvlen):
        for b in range(-(-int(n) // bs)):
            tables[r, b] = perm[used]
            used += 1
    T = int(qlen.sum()) + 3
    q = jnp.asarray(rng.randn(T, nh, hd), jnp.float32)
    pk = jnp.asarray(rng.randn(2, nb, bs, nkv * hd), jnp.float32)
    pv = jnp.asarray(rng.randn(2, nb, bs, nkv * hd), jnp.float32)
    args = (q, pk, pv, tables, qstart, qlen, kvlen)
    got = pra.ragged_paged_attention_pallas(*args, layer=1)
    want = pra.ragged_attention_reference(*args, layer=1)
    live = int(qlen.sum())
    assert np.abs(np.asarray(got - want))[:live].max() < 1e-4
    assert not np.asarray(got)[live:].any()
    assert seen == [((nkv, T * (nh // nkv), hd),) * 2]


# ---------------------------------------------- key width != value width, sink
#: ``(H, Hkv, Dk, Dv, mb, bs, block_q)``: MiMo-V2-Flash's 192 | 128 at a group
#: of 16 (its full layers' 64 on 4, cut to 32 on 2) and of 8 (its window
#: layers'), and a small pair whose value is the WIDER side; ``block_q`` None
#: is the derived block (a plane of whole token tiles: decode rows take the
#: one-token walk), 5 tokens a block has no such tile (every span the general
#: walk)
WIDTH_CASES = {
    "192_128_group16": (32, 2, 192, 128, 4, 16, None),
    "192_128_group8": (16, 2, 192, 128, 4, 16, None),
    "24_16_small": (8, 2, 24, 16, 5, 8, None),
    "16_32_value_wider": (8, 4, 16, 32, 5, 8, None),
    "24_16_general_walk": (8, 2, 24, 16, 5, 8, 5 * 8),
}


def _mk_widths(spans, H, Hkv, Dk, Dv, mb, bs, seed):
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, H, Hkv, Dk, mb, bs,
                                     seed=seed)
    r = np.random.RandomState(seed + 1)
    pv = jnp.asarray(r.randn(pk.shape[0], bs, Hkv, Dv), jnp.float32)
    sink = jnp.asarray(r.randn(H) + 1.0, jnp.float32)
    return (q, pk, pv, tbl, qs, ql, kl), sink


#: MiMo-V2-Flash's group of 16 and the general walk with and without a window
#: and a sink, all four; of the others what the model runs (its window
#: layers' group of 8 under the window with the sink) and one case each side
#: (every case is a trace of its own through the interpreter, 2-4 s)
WIDTH_RUNS = [(case, window, sink)
              for case in ("192_128_group16", "24_16_general_walk")
              for window in (None, 11) for sink in (False, True)] + [
    ("192_128_group8", 11, True), ("24_16_small", 11, True),
    ("16_32_value_wider", None, True), ("16_32_value_wider", 11, False)]


@pytest.mark.parametrize(
    "case, window, sink", WIDTH_RUNS,
    ids=[f"{c}-{'window11' if w else 'full'}-{'sink' if s else 'no_sink'}"
         for c, w, s in WIDTH_RUNS])
def test_key_and_value_widths_and_sink_match_reference(case, window, sink):
    """Keys wider (or narrower) than values and a per-head sink, with and
    without a window, decode rows and spans that cross query blocks in
    one call, over a NaN-poisoned pool: the output is a VALUE wide and the
    oracle's. The sink is one more column of a head's softmax with no
    value, so a row's weights sum to less than one."""
    H, Hkv, Dk, Dv, mb, bs, block_q = WIDTH_CASES[case]
    spans = [(1, mb * bs), (min(5, bs), 12), (0, 0), (bs + 3, 2 * bs + 3),
             (1, 1), (1, 2 * bs)]
    args, b = _mk_widths(spans, H, Hkv, Dk, Dv, mb, bs, seed=H + Dk)
    q, pk, pv, tbl, qs, ql, kl = args
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    kw = dict(window=window, **({"sink": b} if sink else {}))
    got = ragged_paged_attention_pallas(q, pk, pv, tbl, qs, ql, kl,
                                        block_q=block_q, **kw)
    want = ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl, **kw)
    assert got.shape == (q.shape[0], H, Dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sink_is_a_column_without_a_value():
    """The oracle's own rule against the equation: ``p_ij = exp(s_ij) /
    (exp(b_h) + sum_j' exp(s_ij'))``, one decode row over 9 keys by hand."""
    H, Hkv, Dk, Dv, mb, bs = 4, 2, 24, 16, 3, 8
    (q, pk, pv, tbl, qs, ql, kl), b = _mk_widths(
        [(1, 9), (4, 4)], H, Hkv, Dk, Dv, mb, bs, seed=5)
    want = np.asarray(ragged_attention_reference(
        q, pk, pv, tbl, qs, ql, kl, sink=b))
    k = np.asarray(pk)[np.asarray(tbl)[0]].reshape(-1, Hkv, Dk)[:9]
    v = np.asarray(pv)[np.asarray(tbl)[0]].reshape(-1, Hkv, Dv)[:9]
    for h in range(H):
        s = k[:, h // 2] @ np.asarray(q)[0, h] * Dk ** -0.5
        e = np.exp(s - s.max())
        p = e / (np.exp(float(b[h]) - s.max()) + e.sum())
        assert p.sum() < 1.0
        np.testing.assert_allclose(want[0, h], p @ v[:, h // 2], rtol=1e-5,
                                   atol=1e-5)


def test_widths_and_sink_in_the_tiling_and_the_counts():
    """``grid_params`` sizes the query block by the VALUE's width (the
    accumulator's) and an update's pages by both sides of a row; with one
    width it is what it was. The host's counts take that tiling: a window
    counts the keys inside it, whatever the widths."""
    from paddle_tpu.kernels.pallas_ragged_attention import (
        grid_params, ragged_grid_counts)
    same = grid_params(jnp.bfloat16, 32, 1024, 64, 32, 544, head_dim=128)
    assert same == grid_params(jnp.bfloat16, 32, 1024, 64, 32, 544,
                               head_dim=128, value_dim=128)
    # MiMo-V2-Flash's full layers: 64 heads on 4 KV heads, a key 192 lanes, a
    # value 128: 64 tokens a block (the float32 accumulator [4, 1024, 128] is
    # 2 MiB), 256 keys an update
    full = grid_params(jnp.bfloat16, 32, 4 * 192, 1024, 64, 544,
                       head_dim=192, value_dim=128)
    assert full == {"block_q": 64 * 64, "pages": 8, "one_token": True}
    # its window layers: 8 KV heads, a row of 1,536 | 1,024 lanes: 2 MiB of
    # two slots a side hold 204 keys, 6 blocks of 32
    ring = grid_params(jnp.bfloat16, 32, 8 * 192, 1024, 64, 544,
                       head_dim=192, value_dim=128)
    assert ring == {"block_q": 64 * 64, "pages": 6, "one_token": True}
    qs, ql, kl = [0, 1, 2], [1, 1, 512], [300, 9000, 4096]
    got = ragged_grid_counts(qs, ql, kl, heads=64, block_size=32,
                             table_entries=1024, packed_tokens=544,
                             window=128, kv_heads=8, **ring)
    assert got["kv_tokens"] == 128 + 128 + (512 + 127)
    assert got["one_token_rows"] == 2
    # a decode row 9,000 tokens in walks the group(s) that hold its window
    # and not its prefix: at most two groups of 6 pages
    alone = ragged_grid_counts([0], [1], [9000], heads=64, block_size=32,
                               table_entries=1024, packed_tokens=32,
                               window=128, kv_heads=8, **grid_params(
                                   jnp.bfloat16, 32, 1536, 1024, 64, 32,
                                   head_dim=192, value_dim=128))
    assert alone["update_steps"] <= 2 and alone["live_steps"] <= 12
