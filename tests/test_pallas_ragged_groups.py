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


# ------------------- the general walk's own online-softmax update (PR 53)
# the six dense cells' head counts, Phi-4-mini-flash's with its window too
CELL_GROUPS = [(32, 8, None), (16, 16, None), (30, 30, None), (20, 10, None),
               (20, 10, 100), (32, 2, None), (20, 1, None)]
SPAN_UPDATE_CASES = {
    # name: (spans, table entries, packed tokens): blocks of 16, 16 pages an
    # update = 256 keys, two lane tiles of scores a row as on the chip.
    # Spans of 1 and 17 tokens behind 0 and 300 keys share a query block
    # with a dead row and a 150-token chunk behind 300 keys, whose lengths
    # end inside their second group; the buffer ends inside a block
    "mixed": ([(1, 1), (17, 17), (1, 301), (17, 317), (0, 0), (150, 450)],
              32, 200),
    # the same short spans 3,000 keys in: twelve updates a pair
    "deep": ([(1, 3001), (17, 3017)], 192, 18),
}


# (the deep case at the two claimed cells' heads and under the window only:
# every case is a program of its own to lower)
@pytest.mark.parametrize("H,Hkv,window,case", [
    (*cell, case) for cell in CELL_GROUPS for case in sorted(SPAN_UPDATE_CASES)
    if case == "mixed" or cell in [(32, 8, None), (20, 10, 100),
                                   (20, 1, None)]])
def test_span_update_matches_reference_at_the_cells_groups(H, Hkv, window,
                                                           case):
    """``_span_update`` (a row's ``m`` on every lane, ``l`` by lane, its
    lane reduction at the write) against the oracle at every dense cell's
    heads: query blocks of 64 tokens, so the chunk crosses three and its
    first shares one with four other spans; a tall plane (20 / 1, 32 / 2) in
    row chunks. The spans of one token take their own walk where the block
    has a tile. Stale pool rows NaN, unmapped entries sentinels, rows in no
    span exact zeros."""
    spans, mb, T = SPAN_UPDATE_CASES[case]
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, H, Hkv, 16, mb, 16,
                                     seed=H + Hkv, T=T)
    pk, pv, tbl = _sentinels_and_poison(spans, pk, pv, tbl, ql, kl)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=64 * H, pages=16, window=window))
    want = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl,
                                                 window=window))
    used = sum(n for n, _ in spans)
    assert np.isfinite(got).all()
    assert not got[used:].any() and not want[used:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_span_update_on_a_512_token_chunk_in_jambas_blocks():
    """A 512-token chunk behind 300 keys at 20 / 1 in the kernel's own query
    block (192 tokens at heads of 128: 3,840 plane rows in eight row chunks
    of 480, three blocks, the last two thirds full), behind two decode rows
    on their own tiles."""
    from paddle_tpu.kernels.pallas_ragged_attention import grid_params
    spans = [(1, 200), (1, 77), (512, 812)]
    args = _mk(len(spans), spans, 20, 1, 128, 26, 32, seed=53, T=528)
    assert grid_params(jnp.float32, 32, 128, 26, 20, 528, head_dim=128) \
        == dict(block_q=192 * 20, pages=8, one_token=True)
    got = np.asarray(ragged_paged_attention_pallas(*args))
    want = np.asarray(ragged_attention_reference(*args))
    assert not got[514:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


#: sha256 of the float32 outputs below at the parent commit (PR 52's tree,
#: this file's ``_mk`` at seed 53 on the CPU's interpreter): what
#: ``_one_token`` and the ``_softmax_update`` it keeps computed before the
#: general walk got an update of its own
ONE_TOKEN_DIGESTS = {
    None: "e28047fcb2a33fab10af5e176afc2ba4b89b0f0129f87f26df589766ea0f3389",
    24: "5c654037282581e88a9946d2675b0cc7ec10b89335f210e0f80dde23f26b199e",
}


@pytest.mark.parametrize("window", [None, 24])
def test_one_token_walk_is_bit_identical_to_the_parents(window):
    """The decode medians' guard: rows of one token (16 / 4 / 32, a tile of
    their own) over 1 to 200 keys in groups of 48 give, bit for bit, what
    the parent commit's kernel gave: their walk kept ``_softmax_update`` and
    nothing of the general walk's new update reaches it."""
    import hashlib
    spans = [(1, 40), (1, 1), (1, 97), (0, 0), (1, 16), (1, 200), (1, 128)]
    args = _mk(len(spans), spans, 16, 4, 32, 13, 16, seed=53, T=16)
    got = np.asarray(ragged_paged_attention_pallas(
        *args, pages=3, window=window), np.float32)
    assert hashlib.sha256(got.tobytes()).hexdigest() \
        == ONE_TOKEN_DIGESTS[window]
