"""The ragged kernel over an int8 or fp8 pool: the scale planes through the
table-indirect fetch, a group of pages and a decode row's one-token walk
(kernels/pallas_ragged_attention.py). The third file of
``tests/test_pallas_ragged.py``, whose helpers it takes; every case is a
program of its own to lower, so the cases are spread over files and no file is
the floor under the suite's wall (ROADMAP D6).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving.kv_cache import (quantize_kv_rows,
                                         quantize_kv_rows_fp8)

from test_pallas_ragged import (MIXED, _mk, ragged_attention_reference,
                                ragged_paged_attention_pallas)


@pytest.mark.parametrize("block_q", [256, 32])
def test_int8_pool_parity_mixed_spans(block_q):
    """An int8 pool's scale planes ride the same table-indirect fetch as
    their data blocks: kernel and oracle dequantize the same values."""
    q, pk, pv, tbl, qs, ql, kl = _mk(len(MIXED), MIXED, 8, 4, 16, 4, 16,
                                     seed=29)
    (k8, ks), (v8, vs) = quantize_kv_rows(pk), quantize_kv_rows(pv)
    got = ragged_paged_attention_pallas(q, k8, v8, tbl, qs, ql, kl,
                                        block_q=block_q, k_scale=ks,
                                        v_scale=vs)
    want = ragged_attention_reference(q, k8, v8, tbl, qs, ql, kl,
                                      k_scale=ks, v_scale=vs)
    assert not np.asarray(got)[int(sum(n for n, _ in MIXED)):].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("H,Hkv", [(16, 4), (30, 30)])
@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_one_token_walk_of_a_quantized_pool(mode, H, Hkv):
    """A decode row over an int8 or fp8 pool takes the one product over the
    whole pool row too: the group upcast head window by head window, each
    with column k of its scale plane (fp8: a scale a (block, head) that
    differs from page to page). The same rows on the per-head walk (a query
    block that is no whole tile) and the oracle agree; the groups of 3 pages
    end inside the rows' lengths and past them."""
    G = H // Hkv
    tokens = 16 // G
    spans = [(1, 40), (1, 1), (1, 97), (0, 0), (1, 16), (1, 33), (1, 128)]
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, H, Hkv, 32, 8, 16,
                                     seed=H, T=4 * tokens)
    if mode == "int8":
        (k8, ks), (v8, vs) = quantize_kv_rows(pk), quantize_kv_rows(pv)
    else:
        r = np.random.RandomState(41)
        k8, v8 = quantize_kv_rows_fp8(pk), quantize_kv_rows_fp8(pv)
        ks, vs = (jnp.asarray(r.uniform(0.5, 2.0, (pk.shape[0], Hkv)),
                              jnp.float32) for _ in range(2))
    own, general = (np.asarray(ragged_paged_attention_pallas(
        q, k8, v8, tbl, qs, ql, kl, block_q=n * H, k_scale=ks, v_scale=vs,
        pages=3)) for n in (4 * tokens, tokens + 1))
    want = np.asarray(ragged_attention_reference(
        q, k8, v8, tbl, qs, ql, kl, k_scale=ks, v_scale=vs))
    np.testing.assert_allclose(own, general, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(own, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("H,Hkv", [(8, 4), (16, 4)])
@pytest.mark.parametrize("pages", [1, 3])
@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_planes_ride_the_group(mode, pages, H, Hkv):
    """The scale planes of a quantized pool through a group of more than
    one page: int8's per-row planes lie concatenated over the group's pages,
    fp8's per-block scale a factor on the block's rows, column k of the
    plane for KV head k; at groups of 2 and 4."""
    q, pk, pv, tbl, qs, ql, kl = _mk(len(MIXED), MIXED, H, Hkv, 16, 4, 16,
                                     seed=31)
    if mode == "int8":
        (k8, ks), (v8, vs) = quantize_kv_rows(pk), quantize_kv_rows(pv)
    else:
        # the engine's fp8 planes are the constant 1; a scale a (block,
        # head) that differs from page to page shows a factor misplaced
        r = np.random.RandomState(37)
        k8, v8 = quantize_kv_rows_fp8(pk), quantize_kv_rows_fp8(pv)
        ks, vs = (jnp.asarray(r.uniform(0.5, 2.0, (pk.shape[0], Hkv)),
                              jnp.float32) for _ in range(2))
    got = ragged_paged_attention_pallas(
        q, k8, v8, tbl, qs, ql, kl, block_q=4 * H, k_scale=ks, v_scale=vs,
        pages=pages)
    want = ragged_attention_reference(q, k8, v8, tbl, qs, ql, kl,
                                      k_scale=ks, v_scale=vs)
    assert not np.asarray(got)[int(sum(n for n, _ in MIXED)):].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("H,Hkv", [(32, 8), (20, 1)])
@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_pool_through_the_span_update(mode, H, Hkv):
    """A chunk behind a prefix over an int8 or fp8 pool through the general
    walk's own update (PR 53), 256 keys an update as on the chip (two lane
    tiles of scores a row; ``l`` by lane): ``_head_rows`` upcasts a head's
    window with its scale as before, and the chunk's lengths end inside
    their second group. 32 / 8 and a tall plane in row chunks (20 / 1 at 40
    tokens a block)."""
    spans = [(1, 301), (0, 0), (70, 370), (17, 17)]
    q, pk, pv, tbl, qs, ql, kl = _mk(len(spans), spans, H, Hkv, 16, 24, 16,
                                     seed=53 + H, T=96)
    if mode == "int8":
        (k8, ks), (v8, vs) = quantize_kv_rows(pk), quantize_kv_rows(pv)
    else:
        r = np.random.RandomState(43)
        k8, v8 = quantize_kv_rows_fp8(pk), quantize_kv_rows_fp8(pv)
        ks, vs = (jnp.asarray(r.uniform(0.5, 2.0, (pk.shape[0], Hkv)),
                              jnp.float32) for _ in range(2))
    got = ragged_paged_attention_pallas(
        q, k8, v8, tbl, qs, ql, kl, block_q=40 * H, k_scale=ks, v_scale=vs,
        pages=16)
    want = ragged_attention_reference(q, k8, v8, tbl, qs, ql, kl,
                                      k_scale=ks, v_scale=vs)
    assert not np.asarray(got)[88:].any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
