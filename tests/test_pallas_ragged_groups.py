"""The ragged kernel's walk in groups of several pages, per KV head, in row
chunks of a tall plane and on a decode row's own rows
(kernels/pallas_ragged_attention.py): the second file of
``tests/test_pallas_ragged.py``, whose helpers it takes. Every case is a
program of its own to lower, so the cases are spread over files and no file is
the floor under the suite's wall (ROADMAP D6).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from test_pallas_ragged import (_mk, _poison_stale_rows,
                                ragged_attention_reference,
                                ragged_paged_attention_pallas)


def _sentinels_and_poison(spans, pk, pv, tbl, ql, kl):
    """The tables' entries past each span's last block of 16 unmapped
    (sentinels), and every pool row no live span may read NaN."""
    tbl = np.asarray(tbl).copy()
    for r, (_, kvlen) in enumerate(spans):
        tbl[r, -(-kvlen // 16):] = pk.shape[0]
    tbl = jnp.asarray(tbl)
    return (_poison_stale_rows(pk, tbl, kl, ql),
            _poison_stale_rows(pv, tbl, kl, ql), tbl)


# ------------------------------------- the walk in groups of several pages
@pytest.mark.parametrize("pages", [1, 2, 3, 8])
@pytest.mark.parametrize("H,Hkv", [(8, 2), (16, 16)])
def test_mixed_spans_match_reference_at_every_group_size(pages, H, Hkv):
    """One page an update, two, three (the 8-entry table is no whole number
    of groups) and the whole table: decode rows, chunks and a dead row
    against the oracle, on query blocks of 4 tokens (16 rows a plane at H 8,
    the general walk alone; 4 rows at H 16)."""
    spans = [(1, 128), (5, 37), (1, 3), (16, 16), (0, 0), (9, 100)]
    args = _mk(len(spans), spans, H, Hkv, 32, 8, 16, seed=pages + H)
    got = ragged_paged_attention_pallas(*args, pages=pages, block_q=4 * H)
    want = ragged_attention_reference(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


GROUP_EDGE_CASES = {
    # name: (spans, mb): 16-row blocks, 4 pages an update = 64 keys a group
    # the span's own length ends inside the second group
    "kvlen_ends_inside_a_group": ([(1, 70), (6, 90)], 8),
    # a first chunk over two query blocks of 8 tokens: the diagonal of each
    # ends inside a group (tokens 0-7 in the first, 8-15 the same, 16-23 in
    # the second), and the blocks past it hold live rows of the same span
    "diagonal_ends_inside_a_group": ([(40, 100), (1, 5)], 8),
    # the last group starts inside the table and reaches past its end
    "last_group_past_the_table": ([(1, 96), (12, 96)], 6),
}


@pytest.mark.parametrize("case", sorted(GROUP_EDGE_CASES))
def test_group_edges_over_a_poisoned_pool(case):
    """Where a group holds more than the pair may see: entries past the
    pair's last block clamp (to the table's last entry, sentinels into the
    pool) and are masked by ``kvlen`` and the causal rule; stale rows are
    NaN, so any that reached a product would show. Rows in no span are
    exact zeros."""
    spans, mb = GROUP_EDGE_CASES[case]
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, 8, 2, 32, mb, 16,
                                     seed=len(case), T=60)
    pk, pv, tbl = _sentinels_and_poison(spans, pk, pv, tbl, ql, kl)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=64, pages=4))
    want = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl))
    used = sum(n for n, _ in spans)
    assert np.isfinite(got).all()
    assert not got[used:].any() and not want[used:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,Hkv", [(16, 4), (32, 8), (16, 16), (8, 1),
                                   (12, 4), (20, 1), (40, 2)])
def test_one_token_walk_equals_general_walk(H, Hkv):
    """A decode row takes ONE product over the whole pool row, its ``H``
    query rows cut out of the head-major block and laid block-diagonal in
    VMEM, where the query block is whole tiles of ``lcm(16, G)`` rows, whole
    tokens in whole 16-row tiles, so no token straddles two: 16 rows where
    ``G`` divides 16, 48 (16 tokens, 3 row tiles) at ``G`` 3, which took the
    general walk before PR 51 and has a tile of its own like every other
    group since, 80 (4 tokens, 5 row tiles) at Jamba2-3B's 20; the per-head
    walk on the whole block only where a block is no whole number of tiles
    (a block of one token more). Both walks on the same rows give the same
    numbers within float32 rounding, and both match the oracle."""
    import math

    from paddle_tpu.kernels.pallas_ragged_attention import (_token_tile,
                                                            grid_params)
    G = H // Hkv
    tile_tokens = math.lcm(16, G) // G
    assert _token_tile(4 * tile_tokens * G, G) == tile_tokens * G
    assert not _token_tile((tile_tokens + 1) * G, G)
    spans = [(1, 40), (1, 1), (1, 97), (0, 0), (1, 16), (1, 33), (1, 128)]
    args = _mk(len(spans), spans, H, Hkv, 32, 8, 16, seed=H,
               T=4 * tile_tokens)
    tiling = [grid_params(jnp.float32, 16, Hkv * 32, 8, H, 4 * tile_tokens,
                          block_q=n * H, head_dim=32)
              for n in (4 * tile_tokens, tile_tokens + 1)]
    assert [t["one_token"] for t in tiling] == [True, False]
    # (a one-byte pool has the same walks)
    assert grid_params(jnp.int8, 16, Hkv * 32, 8, H, 4 * tile_tokens,
                       head_dim=32)["one_token"]
    own = np.asarray(ragged_paged_attention_pallas(
        *args, block_q=4 * tile_tokens * H, pages=3))
    general = np.asarray(ragged_paged_attention_pallas(
        *args, block_q=(tile_tokens + 1) * H, pages=3))
    np.testing.assert_allclose(own, general, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        own, np.asarray(ragged_attention_reference(*args)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,Hkv", [(32, 8), (16, 16), (30, 30), (12, 4),
                                   (8, 1), (8, 4)])
def test_per_head_walk_matches_reference(H, Hkv):
    """Each KV head's keys by that head's queries only, at the cells' head
    counts and at groups of 3, 8 and 2: query blocks of 16 tokens over 60
    packed rows, so the chunks cross blocks, end inside a group of pages and
    share blocks with one-token spans and a dead row; the pool stale rows
    NaN; packed rows in no span exact zeros."""
    spans = [(1, 70), (21, 90), (1, 3), (0, 0), (1, 128), (30, 100)]
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, H, Hkv, 16, 8, 16,
                                     seed=H + Hkv, T=60)
    pk, pv, tbl = _sentinels_and_poison(spans, pk, pv, tbl, ql, kl)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=16 * H, pages=3))
    want = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl))
    used = sum(n for n, _ in spans)
    assert np.isfinite(got).all()
    assert not got[used:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------- a plane taller than one score tile's rows
# decode rows around a chunk with a prefix, a dead row, a first chunk (its
# early rows' diagonal ends groups before its last rows') and a chunk that
# ends inside a row chunk: 95 tokens in a packed buffer of 100
TALL_SPANS = [(1, 200), (1, 33), (37, 150), (0, 0), (1, 256), (45, 45),
              (1, 1), (9, 100)]


@pytest.mark.parametrize("block_tokens", [None, 48], ids=["whole", "48"])
@pytest.mark.parametrize("H,Hkv", [(20, 1), (32, 2)])
def test_tall_planes_walk_in_row_chunks(H, Hkv, block_tokens):
    """Jamba2-3B's and Nemotron-3-Nano's groups (20 and 16 query heads a KV
    head) at a query block whose plane is taller than ``_PLANE_ROWS``: the
    block ``query_block_rows`` gives (the accumulator's, here the whole
    buffer: 2,000 and 1,600 rows, four row chunks) and one of 48 tokens (960
    and 768 rows, two chunks, three blocks). The general walk takes the plane
    in static row chunks and skips a chunk that holds no row of the pair's
    span or no key under its diagonal; every pair resets and writes back its
    own chunks only, so a chunk computed without its reset, or written back
    without being computed, shows against the oracle; decode rows take their
    own tile (80 rows at 20). Stale pool rows are NaN and unmapped table
    entries sentinels; packed rows in no span are exact zeros."""
    from paddle_tpu.kernels.pallas_ragged_attention import (_PLANE_ROWS,
                                                            _plane_rows,
                                                            _row_chunks,
                                                            grid_params)
    G, T = H // Hkv, 100
    q, pk, pv, tbl, qs, ql, kl = _mk(len(TALL_SPANS), TALL_SPANS, H, Hkv, 32,
                                     16, 16, seed=H, T=T)
    block_q = None if block_tokens is None else block_tokens * H
    tiling = grid_params(jnp.float32, 16, Hkv * 32, 16, H, T, block_q,
                         pages=2, head_dim=32)
    rows = _plane_rows(tiling["block_q"], H, G, T)
    chunks = _row_chunks(rows)
    assert rows > _PLANE_ROWS and len(chunks) == (2 if block_tokens else 4)
    assert all(n <= _PLANE_ROWS and n % 16 == 0 for _, n in chunks)
    assert sum(n for _, n in chunks) == rows
    assert tiling["one_token"]
    pk, pv, tbl = _sentinels_and_poison(TALL_SPANS, pk, pv, tbl, ql, kl)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=block_q, pages=2))
    want = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl))
    used = sum(n for n, _ in TALL_SPANS)
    assert np.isfinite(got).all()
    assert not got[used:].any() and not want[used:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,Hkv", [(20, 1), (40, 2)])
def test_tall_planes_without_a_token_tile_take_the_chunked_walk(H, Hkv):
    """The same spans at a group of 20 and a block of 49 tokens, no whole
    number of 4-token tiles (at a group that divides 16 every block is): a
    span of one token takes the general walk, which computes on the row
    chunk that holds it and skips the others."""
    from paddle_tpu.kernels.pallas_ragged_attention import grid_params
    args = _mk(len(TALL_SPANS), TALL_SPANS, H, Hkv, 32, 16, 16, seed=H,
               T=100)
    assert not grid_params(jnp.float32, 16, Hkv * 32, 16, H, 100, 49 * H,
                           pages=3, head_dim=32)["one_token"]
    got = np.asarray(ragged_paged_attention_pallas(*args, block_q=49 * H,
                                                   pages=3))
    np.testing.assert_allclose(
        got, np.asarray(ragged_attention_reference(*args)), rtol=2e-5,
        atol=2e-5)
