"""The ragged kernel's KV pipeline across its (query block, row) pairs
(kernels/pallas_ragged_attention.py, ``_groups``): a pair's first group of
pool pages is started by the pair before it, where two work-list entries in
a row are live, and only waited for by the pair itself. Interpret mode
against ``ragged_attention_reference`` over NaN-poisoned pools, one compiled
call for all cases, plus the host's ``prefetched_pairs`` against the kernel's
rule on ``_work_list``'s own arrays.

A file of its own, beside ``test_pallas_ragged.py``: under ``--dist loadfile``
a file runs on one worker, and that file is already the longest of the run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.pallas_ragged_attention import (
    _first_slots, _hands_over, _work_list, ragged_attention_reference,
    ragged_grid_counts, ragged_paged_attention_pallas)
from paddle_tpu.serving.kv_cache import (quantize_kv_rows,
                                         quantize_kv_rows_fp8)

from test_pallas_ragged import _mk, _poison_stale_rows

# (qstart, qlen, kvlen) of 8 rows over 32 packed tokens: query blocks of 4
# tokens, 16-row pool blocks, 2 pages an update = 32 keys a group, 8 table
# entries = at most 4 groups a pair
HANDOVER_CASES = {
    # decode rows whose walks are 1, 2, 3, 4, 2, 3, 1, 4 groups: the slot a
    # pair starts in flips or not with the pair before
    "odd_and_even_group_counts_alternate": (
        [0, 1, 2, 3, 4, 5, 6, 7], [1] * 8,
        [20, 40, 70, 128, 33, 96, 10, 100]),
    "one_group_row_between_two_long_ones": (
        [0, 1, 2, 0, 0, 0, 0, 0], [1, 1, 1, 0, 0, 0, 0, 0],
        [128, 5, 120, 0, 0, 0, 0, 0]),
    # a dead row between live ones, then query blocks 1-2 untouched (dead
    # entries: nothing is handed across them), then live pairs again
    "dead_row_and_untouched_blocks_between_live_pairs": (
        [0, 1, 1, 12, 13, 14, 14, 30], [1, 0, 2, 1, 1, 0, 1, 1],
        [70, 99, 40, 100, 33, 64, 128, 17]),
    # a chunk over five query blocks, then decode rows: the general walk
    # hands over to the one-token walk
    "chunk_blocks_then_decode_rows": (
        [0, 20, 21, 22, 23, 0, 0, 0], [20, 1, 1, 1, 1, 0, 0, 0],
        [100, 70, 128, 20, 90, 0, 0, 0]),
    # ... and the reverse; the chunk starts inside the decode rows' block
    "decode_rows_then_chunk_blocks": (
        [0, 1, 2, 3, 0, 0, 0, 0], [1, 1, 1, 22, 0, 0, 0, 0],
        [33, 128, 60, 120, 0, 0, 0, 0]),
}
_HANDOVER = dict(R=8, T=32, H=8, Hkv=2, D=32, mb=8, bs=16, pages=2,
                 tokens=4)


def _handover_args(case, H=_HANDOVER["H"], Hkv=_HANDOVER["Hkv"], seed=0):
    """The kernel's arguments for a ``HANDOVER_CASES`` entry: every table
    entry past a row's length a sentinel, every pool row no live row may
    read NaN."""
    g = _HANDOVER
    qstart, qlen, kvlen = HANDOVER_CASES[case]
    q, pk, pv, tbl, _, _, _ = _mk(g["R"], [(1, 1)] * g["R"], H, Hkv, g["D"],
                                  g["mb"], g["bs"], seed=seed, T=g["T"])
    tbl = np.asarray(tbl).copy()
    for r, kl in enumerate(kvlen):
        tbl[r, -(-kl // g["bs"]):] = pk.shape[0]
    qs, ql, kl = (jnp.asarray(x, jnp.int32) for x in (qstart, qlen, kvlen))
    return q, pk, pv, jnp.asarray(tbl), qs, ql, kl


@pytest.fixture(scope="module")
def handover_kernel():
    """One compiled call a (window, quantized) pair: the cases differ in
    their span metadata only."""
    import functools

    @functools.partial(jax.jit, static_argnames="window")
    def call(q, pk, pv, tbl, qs, ql, kl, ks=None, vs=None, *, window=None):
        return ragged_paged_attention_pallas(
            q, pk, pv, tbl, qs, ql, kl,
            block_q=_HANDOVER["tokens"] * q.shape[1],
            pages=_HANDOVER["pages"], k_scale=ks, v_scale=vs, window=window)
    return call


def _assert_handover(got, want, qstart, qlen, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    in_span = np.zeros(got.shape[0], bool)
    for s, n in zip(qstart, qlen):
        in_span[s:s + n] = True
    assert np.isfinite(got).all()
    assert not got[~in_span].any()
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [None, 20])
@pytest.mark.parametrize("case", sorted(HANDOVER_CASES))
def test_first_group_handed_over_matches_reference(case, window,
                                                   handover_kernel):
    """A pair's first group of pool pages is started by the pair before it
    (two live work-list entries in a row) into the slot that pair's last
    group does not use, and only waited for by the pair itself. Interpret
    mode copies at ``start``, so what these cases catch is a wrong slot, a
    wrong row or group, a copy nobody started or one started across a dead
    entry, NOT a read before the data landed: that guard is the chip's
    (``chip_smoke.py``, the kernels leg's poisoned hand-over cases). Under
    the window of 20 a long row's first group lies above 0 (positions 108
    on: group 3), so the group handed over is not group 0."""
    args = _handover_args(case, seed=len(case))
    q, pk, pv, tbl, qs, ql, kl = args
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    got = handover_kernel(q, pk, pv, tbl, qs, ql, kl, window=window)
    want = ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl,
                                      window=window)
    _assert_handover(got, want, *HANDOVER_CASES[case][:2])


@pytest.mark.parametrize("mode", ["int8", "fp8"])
@pytest.mark.parametrize("case", ["odd_and_even_group_counts_alternate",
                                  "chunk_blocks_then_decode_rows"])
def test_handed_over_group_of_a_quantized_pool(case, mode, handover_kernel):
    """K, V and both scale planes cross the pair boundary as one set of
    copies; an int8 pool's scales are NaN wherever no live row may read."""
    q, pk, pv, tbl, qs, ql, kl = _handover_args(case, seed=7)
    if mode == "int8":
        (k8, ks), (v8, vs) = quantize_kv_rows(pk), quantize_kv_rows(pv)
        ks = _poison_stale_rows(ks, tbl, kl, ql)
        vs = _poison_stale_rows(vs, tbl, kl, ql)
    else:
        r = np.random.RandomState(43)
        k8, v8 = quantize_kv_rows_fp8(pk), quantize_kv_rows_fp8(pv)
        ks, vs = (jnp.asarray(r.uniform(0.5, 2.0, (pk.shape[0], pk.shape[2])),
                              jnp.float32) for _ in range(2))
    got = handover_kernel(q, k8, v8, tbl, qs, ql, kl, ks, vs)
    want = ragged_attention_reference(q, k8, v8, tbl, qs, ql, kl,
                                      k_scale=ks, v_scale=vs)
    _assert_handover(got, want, *HANDOVER_CASES[case][:2], tol=1e-4)


@pytest.mark.parametrize("case", ["odd_and_even_group_counts_alternate",
                                  "decode_rows_then_chunk_blocks"])
def test_handed_over_group_where_every_span_takes_the_general_walk(
        case, handover_kernel):
    """``G`` 3: a token's rows could straddle two tiles, so a decode row
    takes the per-head walk like a chunk, and hands over like one."""
    q, pk, pv, tbl, qs, ql, kl = _handover_args(case, H=12, Hkv=4, seed=5)
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    got = handover_kernel(q, pk, pv, tbl, qs, ql, kl)
    want = ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl)
    _assert_handover(got, want, *HANDOVER_CASES[case][:2])


def _random_spans(rng, R, T):
    """Disjoint spans in packed order over ``T`` tokens with holes between
    them, some rows dead, and a length for each."""
    cuts = np.sort(rng.choice(np.arange(T + 1), 2 * R, replace=False))
    qstart = cuts[0::2].astype(np.int32)
    qlen = (cuts[1::2] - cuts[0::2]).astype(np.int32)
    qlen[rng.rand(R) < 0.25] = 0
    qlen = np.where(rng.rand(R) < 0.3, np.minimum(qlen, 1), qlen)
    kvlen = (qlen + rng.randint(0, 100, R)).astype(np.int32)
    return qstart, qlen, kvlen


@pytest.mark.parametrize("window,pages", [(None, 1), (None, 3), (24, 2)])
def test_prefetched_pairs_is_the_kernels_rule_on_the_work_list(window,
                                                               pages):
    """``ragged_grid_counts(...)["prefetched_pairs"]`` against the rule the
    kernel applies (``_hands_over``) to ``_work_list``'s own arrays, over
    random span metadata; and the slots (``_first_slots``): a pair's first
    group lies in the slot the last group of the entry before it does not
    use, so what that entry starts there is what the pair waits for."""
    R, T, heads, tokens, bs, mb = 6, 48, 4, 8, 16, 8
    geometry = dict(nq=T // tokens, tokens_per_block=tokens, block_size=bs,
                    table_entries=mb)

    @jax.jit
    def listed(a, b, c):
        work = _work_list(a, b, c, **geometry, window=window, pages=pages)
        wlo = work[4] if window is not None else jnp.zeros_like(work[3])
        return work[3], wlo, _first_slots(work[3], wlo, pages)

    rng = np.random.RandomState(42)
    seen = set()
    for _ in range(40):
        qstart, qlen, kvlen = _random_spans(rng, R, T)
        wn, wlo, slots = (np.asarray(a) for a in listed(
            *(jnp.asarray(x) for x in (qstart, qlen, kvlen))))
        handed = _hands_over(wn[:-1], wn[1:])
        got = ragged_grid_counts(
            qstart, qlen, kvlen, heads=heads, block_size=bs,
            table_entries=mb, packed_tokens=T, block_q=tokens * heads,
            pages=pages, window=window)
        assert got["prefetched_pairs"] == int(handed.sum())
        seen.add(got["prefetched_pairs"])
        groups = np.where(wn > 0, -(-wn // pages) - wlo // pages, 0)
        assert (groups[wn > 0] > 0).all()
        last = (slots + groups - 1) % 2         # slot of an entry's last group
        assert (slots[1:][handed] == 1 - last[:-1][handed]).all()
        assert slots[0] == 0
    assert len(seen) > 3                        # the draws differ


@pytest.mark.parametrize("rows", [1, 5, 24, 48])
def test_prefetched_pairs_of_live_decode_rows_in_one_query_block(rows):
    got = ragged_grid_counts(
        np.arange(rows), np.ones(rows, np.int32), 100 + np.arange(rows),
        heads=8, block_size=16, table_entries=16, packed_tokens=rows,
        block_q=128 * 8, pages=4)
    assert got["prefetched_pairs"] == rows - 1
    assert got["update_steps"] == sum(-(-(100 + i) // 64) for i in range(rows))
