"""The ragged kernel's walk in groups of several pages, per KV head and on a
decode row's own rows (kernels/pallas_ragged_attention.py): the second file of
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
    tbl = np.asarray(tbl).copy()
    for r, (_, kvlen) in enumerate(spans):
        tbl[r, -(-kvlen // 16):] = pk.shape[0]      # unmapped -> sentinel
    tbl = jnp.asarray(tbl)
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=64, pages=4))
    want = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl))
    used = sum(n for n, _ in spans)
    assert np.isfinite(got).all()
    assert not got[used:].any() and not want[used:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("H,Hkv,walks", [
    (16, 4, "one_token"), (32, 8, "one_token"), (16, 16, "one_token"),
    (8, 1, "one_token"), (12, 4, "general")])
def test_one_token_walk_equals_general_walk(H, Hkv, walks):
    """A decode row takes ONE product over the whole pool row, its ``H``
    query rows cut out of the head-major block and laid block-diagonal in
    VMEM, where the query block is whole 16-row tiles and no token straddles
    two (``G`` divides 16); the per-head walk on the whole block otherwise
    (``G`` 3, or a block of 5 / 17 / 3 tokens). Both walks on the same rows
    give the same numbers within float32 rounding, and both match the
    oracle."""
    from paddle_tpu.kernels.pallas_ragged_attention import (_token_tile,
                                                            grid_params)
    G = H // Hkv
    tile_tokens = 16 // G if 16 % G == 0 else 16
    assert bool(_token_tile(4 * tile_tokens * G, G)) == (walks == "one_token")
    assert not _token_tile((tile_tokens + 1) * G, G)
    spans = [(1, 40), (1, 1), (1, 97), (0, 0), (1, 16), (1, 33), (1, 128)]
    args = _mk(len(spans), spans, H, Hkv, 32, 8, 16, seed=H,
               T=4 * tile_tokens)
    tiling = [grid_params(jnp.float32, 16, Hkv * 32, 8, H, 4 * tile_tokens,
                          block_q=n * H, head_dim=32)
              for n in (4 * tile_tokens, tile_tokens + 1)]
    assert [t["one_token"] for t in tiling] == [walks == "one_token", False]
    # (a one-byte pool has the same walks)
    assert grid_params(jnp.int8, 16, Hkv * 32, 8, H, 4 * tile_tokens,
                       head_dim=32)["one_token"] == (walks == "one_token")
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
    tbl = np.asarray(tbl).copy()
    for r, (_, kvlen) in enumerate(spans):
        tbl[r, -(-kvlen // 16):] = pk.shape[0]      # unmapped -> sentinel
    tbl = jnp.asarray(tbl)
    pk = _poison_stale_rows(pk, tbl, kl, ql)
    pv = _poison_stale_rows(pv, tbl, kl, ql)
    got = np.asarray(ragged_paged_attention_pallas(
        q, pk, pv, tbl, qs, ql, kl, block_q=16 * H, pages=3))
    want = np.asarray(ragged_attention_reference(q, pk, pv, tbl, qs, ql, kl))
    used = sum(n for n, _ in spans)
    assert np.isfinite(got).all()
    assert not got[used:].any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
