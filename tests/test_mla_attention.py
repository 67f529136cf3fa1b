"""Latent (MLA) attention: the absorbed form the step programs run, the
expanded form of whole-prompt prefill, the kernel's expanded-form oracle and
the plain reference agree on one layer; the Pallas kernel (interpret mode)
equals its oracle over a paged latent pool with mixed spans; the pool stores
a token's 576 values once (ISSUE 31)."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels.pallas_mla_ragged_attention import (
    latent_row_width, mla_ragged_attention_pallas,
    mla_ragged_attention_reference)
from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM, Mla,
                                           deepseek_v2_tiny)
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving.decode import latent_rows, mla_expanded_attention

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
import reference_deepseek_v2 as ref  # noqa: E402

H, RANK, NOPE, ROPE, V, BS = 4, 32, 16, 8, 16, 8
MLA = Mla(RANK, NOPE, ROPE, V, 0.37, None)
W = latent_row_width(RANK, ROPE)


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _pool(rng, lengths, tables, nb, layers=2, layer=1):
    """A latent pool whose layer ``layer`` holds, through ``tables``, the
    first ``lengths[r]`` rows of each sequence; every other row is noise (a
    stale block), so a read past a length would show."""
    pool = np.asarray(_rand(rng, layers, nb, BS, W)) * 3.0
    c_kv, k_pe = [], []
    for r, n in enumerate(lengths):
        c, k = _rand(rng, n, RANK), _rand(rng, n, ROPE)
        rows = np.asarray(latent_rows(c, k))[:, 0]
        for t in range(n):
            pool[layer, tables[r][t // BS], t % BS] = rows[t]
        c_kv.append(c)
        k_pe.append(k)
    return jnp.asarray(pool), c_kv, k_pe


def _absorbed(q_nope, q_pe, w_kvb, pool, tables, qs, ql, kl, **kw):
    w = w_kvb.reshape(RANK, H, NOPE + V)
    q_lat = jnp.einsum("thd,rhd->thr", q_nope, w[..., :NOPE])
    o_lat = mla_ragged_attention_pallas(q_lat, q_pe, pool, tables, qs, ql,
                                        kl, scale=MLA.scale, layer=1, **kw)
    return jnp.einsum("thr,rhd->thd", o_lat, w[..., NOPE:])


def _jitted(fn, *args, **static):
    """``fn`` as one compiled program: called eagerly, every ``jnp`` op
    around the kernel (the work list alone is dozens) compiles on its own."""
    return jax.jit(functools.partial(fn, **static))(*args)


def test_absorbed_expanded_oracle_and_reference_agree():
    """One sequence, every position a query: four computations of the same
    attention."""
    rng = np.random.default_rng(0)
    n = 21
    tables = np.asarray([[2, 0, 3]], np.int32)
    pool, (c_kv,), (k_pe,) = _pool(rng, [n], tables, nb=4)
    q_nope, q_pe = _rand(rng, n, H, NOPE), _rand(rng, n, H, ROPE)
    w_kvb = _rand(rng, RANK, H * (NOPE + V)) * 0.3
    span = (tables, [0], [n], [n])
    span = tuple(np.asarray(x, np.int32) for x in span)
    absorbed = _jitted(_absorbed, q_nope, q_pe, w_kvb, pool, *span, pages=2)
    oracle = _jitted(mla_ragged_attention_reference,
                     q_nope, q_pe, w_kvb, pool, *span, scale=MLA.scale,
                     layer=1)
    expanded = mla_expanded_attention(q_nope[None], q_pe[None], c_kv[None],
                                      k_pe[None], w_kvb, mla=MLA)[0]
    kv = (c_kv @ w_kvb).reshape(n, H, NOPE + V)
    k = jnp.concatenate([kv[..., :NOPE],
                         jnp.broadcast_to(k_pe[:, None], (n, H, ROPE))], -1)
    plain = ref._attention(jnp.concatenate([q_nope, q_pe], -1), k,
                           kv[..., NOPE:], MLA.scale)
    for name, got in (("absorbed", absorbed), ("oracle", oracle),
                      ("expanded", expanded)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   atol=2e-5, rtol=1e-4, err_msg=name)


#: name: (rows as (span start, span length, kv length after the step),
#: packed tokens, kernel options). Tables are a permutation of the blocks.
SPANS = {
    "decode_rows": ([(0, 1, 9), (1, 1, 24), (2, 1, 1)], 4, {}),
    "chunk_with_cached_prefix": ([(0, 1, 17), (1, 12, 29)], 16, {}),
    "dead_rows_between": ([(0, 1, 5), (0, 0, 0), (1, 7, 7), (0, 0, 13)], 10,
                          {}),
    "row_ending_mid_block": ([(0, 5, 13), (5, 1, 19)], 8, {}),
    "several_groups_and_query_blocks": ([(0, 1, 30), (1, 20, 31), (21, 1, 3)],
                                        24, dict(pages=2, block_q=32)),
    "one_page_a_group": ([(0, 3, 27), (3, 1, 16)], 4, dict(pages=1)),
}


# (16 heads are whole tiles: a one-token span then walks on its own rows of
# the query block, the kernel's second path)
@pytest.mark.parametrize("case,heads", [(c, 4) for c in sorted(SPANS)] + [
    ("decode_rows", 16), ("dead_rows_between", 16),
    ("several_groups_and_query_blocks", 16)])
def test_kernel_equals_oracle_over_paged_pool(case, heads, monkeypatch):
    monkeypatch.setattr(sys.modules[__name__], "H", heads)
    rows, packed, opts = SPANS[case]
    rng = np.random.default_rng(len(case))
    mb = 4
    order = rng.permutation(len(rows) * mb)
    tables = order.reshape(len(rows), mb).astype(np.int32)
    # unmapped entries past a row's blocks are the sentinel
    for r, (_, _, kl) in enumerate(rows):
        tables[r, -(-kl // BS):] = len(rows) * mb
    pool, _, _ = _pool(rng, [kl for _, _, kl in rows], tables,
                       nb=len(rows) * mb)
    qs, ql, kl = (np.asarray(x, np.int32) for x in zip(*rows))
    q_nope, q_pe = _rand(rng, packed, H, NOPE), _rand(rng, packed, H, ROPE)
    w_kvb = _rand(rng, RANK, H * (NOPE + V)) * 0.3
    got = _jitted(_absorbed, q_nope, q_pe, w_kvb, pool, tables, qs, ql, kl,
                  **opts)
    want = _jitted(mla_ragged_attention_reference,
                   q_nope, q_pe, w_kvb, pool, tables, qs, ql, kl,
                   scale=MLA.scale, layer=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)
    live = np.zeros(packed, bool)
    for s, n, _ in rows:
        live[s:s + n] = True
    assert not np.asarray(got)[~live].any()     # exact zeros off every span
    assert np.abs(np.asarray(got)[live]).min() > 0


def test_pool_holds_576_values_a_token_once():
    """At the published latent widths (512 + 64) a cached token is one row
    of 640 lanes on the K side, 576 of them written, and nothing on the V
    side: no per-head K or V is cached, and no second copy of the latent."""
    paddle.seed(5)
    model = DeepseekV2ForCausalLM(deepseek_v2_tiny(
        kv_lora_rank=512, qk_rope_head_dim=64, decode_attention="jnp"))
    eng = ContinuousBatchingEngine(model, num_slots=2, max_seq_len=64,
                                   prefill_chunk=32, decode_chunk=1)
    pool, layers = eng.cache.pool, model.config.num_hidden_layers
    assert pool.k.shape[-1] == 640 and pool.v.shape[-1] == 0
    assert eng.cache.bytes_per_token() == layers * 640 * 4      # float32
    assert eng.cache.occupancy_bytes()["per_token"] == layers * 640 * 4
    prompt = np.random.RandomState(0).randint(1, 256, 40).tolist()
    eng.generate([GenerationRequest(prompt, max_new_tokens=3)])
    rows = np.asarray(pool.k).reshape(layers, -1, 640)
    written = np.abs(rows).sum(-1) > 0
    assert (written.sum(1) == 40 + 2).all()     # the last token is not cached
    assert (np.abs(rows[written][:, :576]) > 0).all()
    assert not rows[..., 576:].any()
