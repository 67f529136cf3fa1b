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
from paddle_tpu.kernels import pallas_mla_ragged_attention as mla_mod
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
    # (every case without ``pages`` walks a table of 4 entries, shorter than
    # a group of ``PAGES``: a group is then the whole table. This one by name)
    "table_shorter_than_a_group": ([(0, 1, 27), (1, 1, 32), (2, 3, 9)], 8,
                                   {}),
}


# (16 heads are whole tiles: a one-token span then walks on its own rows of
# the query block, the kernel's second path)
@pytest.mark.parametrize("case,heads", [(c, 4) for c in sorted(SPANS)] + [
    ("decode_rows", 16), ("dead_rows_between", 16),
    ("several_groups_and_query_blocks", 16),
    ("table_shorter_than_a_group", 16)])
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


#: groups of pool pages a (query block, row) pair walks, around the edges of
#: the walk's pipeline (``_walk_ahead``): a dead pair; fewer groups than the
#: ``SLOTS - 1`` a pair starts ahead (no steady loop at all); exactly that
#: many; one more (one steady iteration); and a pair that goes round the
#: slots more than twice. The last case: a row that ends ON a group's edge
EDGE_GROUPS = sorted({0, 1, max(mla_mod.SLOTS - 2, 1), mla_mod.SLOTS - 1,
                      mla_mod.SLOTS, 2 * mla_mod.SLOTS + 1})
EDGE_CASES = EDGE_GROUPS + ["on_a_groups_edge"]
EDGE_PAGES = 2                  # table entries a group: 16 keys at BS = 8
EDGE_ENTRIES = EDGE_GROUPS[-1] * EDGE_PAGES


def edge_groups(case):
    return EDGE_GROUPS[-2] if case == "on_a_groups_edge" else case


def edge_spans(n_q):
    """``[(query span, kv length)]``, a row for each of ``EDGE_CASES`` in
    order (``tests/test_dsa_kernels.py`` walks the same rows under a
    selection): a row of ``n`` groups ends inside its last group, in the
    group's first page or its second in turn, so the group's other keys are
    a stale block's or the sentinel's; 0 groups is a dead row."""
    group = EDGE_PAGES * BS
    return [(n_q, group * (n - 1) + (3, BS + 3)[i % 2]) if n else (0, 0)
            for i, n in enumerate(EDGE_GROUPS)] \
        + [(n_q, group * EDGE_GROUPS[-2])]


@functools.lru_cache(maxsize=None)
def _edge_walk(path):
    """(kernel output, oracle, rows) of ONE call at 16 heads over
    ``edge_spans``. ``path`` "one_token": decode rows, each on its own wide
    rows; "span": spans of three tokens, the query block's whole width."""
    spans = edge_spans({"one_token": 1, "span": 3}[path])
    starts = np.concatenate([[0], np.cumsum([q for q, _ in spans])])
    rows = [(int(at), q, kl) for at, (q, kl) in zip(starts, spans)]
    packed = -(-int(starts[-1]) // 8) * 8
    rng = np.random.default_rng(len(path))
    nb = len(rows) * EDGE_ENTRIES
    tables = rng.permutation(nb).reshape(len(rows), -1).astype(np.int32)
    for r, (_, _, kl) in enumerate(rows):
        tables[r, -(-kl // BS):] = nb
    pool, _, _ = _pool(rng, [kl for _, _, kl in rows], tables, nb=nb)
    qs, ql, kl = (np.asarray(x, np.int32) for x in zip(*rows))
    q_nope, q_pe = _rand(rng, packed, H, NOPE), _rand(rng, packed, H, ROPE)
    w_kvb = _rand(rng, RANK, H * (NOPE + V)) * 0.3
    got = _jitted(_absorbed, q_nope, q_pe, w_kvb, pool, tables, qs, ql, kl,
                  pages=EDGE_PAGES)
    want = _jitted(mla_ragged_attention_reference, q_nope, q_pe, w_kvb, pool,
                   tables, qs, ql, kl, scale=MLA.scale, layer=1)
    assert mla_mod.grid_params(EDGE_ENTRIES, H, packed, pages=EDGE_PAGES)[
        "one_token"]
    return np.asarray(got), np.asarray(want), rows


@pytest.mark.parametrize("path", ["one_token", "span"])
@pytest.mark.parametrize("case", EDGE_CASES)
def test_a_pair_walks_its_groups_at_the_pipelines_edges(case, path,
                                                        monkeypatch):
    """Each row of ``_edge_walk`` against the oracle: a pair of fewer groups
    than the walk starts ahead, of exactly as many, of one more and of
    several rounds of the slots reads every key of its row once and none of
    another's (the pool's other rows are noise)."""
    monkeypatch.setattr(sys.modules[__name__], "H", 16)
    got, want, rows = _edge_walk(path)
    if case == 0:
        # the dead row's pair walks nothing and no other pair missed it:
        # rows outside every span are exact zeros
        live = np.zeros(got.shape[0], bool)
        for s, n, _ in rows:
            live[s:s + n] = True
        assert not got[~live].any() and (~live).any()
        return
    s, n, kl = rows[EDGE_CASES.index(case)]
    assert -(-kl // (EDGE_PAGES * BS)) == edge_groups(case)
    np.testing.assert_allclose(got[s:s + n], want[s:s + n], atol=2e-5,
                               rtol=1e-4)
    assert np.abs(got[s:s + n]).min() > 0


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
