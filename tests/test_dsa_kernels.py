"""Sparse attention's three steps (``kernels/dsa.py``; ISSUE 43) on the CPU:
the two Pallas kernels in interpret mode and the selection against their
``jax.numpy`` oracles over NaN-poisoned pools (decode rows, a chunk with a
cached prefix, a dead row, rows outside every span), ties at the threshold
included; and the sigmoid router with a
selection bias against the reference's rule; and a tree with no indexer
lowering to the programs PR 42's tree lowered to, to the letter."""
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.kernels import dsa
from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.kernels.pallas_ragged_attention import NEG_INF
from paddle_tpu.models.deepseek_v2 import (DeepseekV2ForCausalLM,
                                           deepseek_v2_tiny)
from paddle_tpu.serving import decode as decode_mod

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark"))
import reference_glm_moe_dsa as ref  # noqa: E402
import test_mla_attention as edges  # noqa: E402

BS, MB = 8, 12                  # block size, table entries: 96 positions
NH, RANK, ROPE, NOPE, VD, W = 4, 32, 8, 16, 16, 128
HI, D, TOPK = 4, 16, 8

# (query span, kv length after this step); span 0 is a dead row
SPANS = {
    "decode_rows": [(1, 1), (1, 9), (1, 37), (1, 64), (1, 90)],
    "chunk_behind_decode_rows": [(1, 30), (20, 52), (1, 77), (0, 0)],
    "chunk_from_zero": [(24, 24), (1, 11)],
}
# for an indexer of 16 or 32 heads, where a span of ONE token scores on its
# own wide rows (ISSUE 44): a context of one key, one that ends inside a
# group (of 4 table entries = 32 keys), one on a group's edge, a dead row;
# decode rows before AND behind a chunk inside one query block; a fresh
# prompt of one token alone
ONE_TOKEN_SPANS = {
    "decode_rows_alone": [(1, 1), (1, 37), (1, 64), (0, 0), (1, 90)],
    "decode_rows_around_a_chunk": [(1, 30), (1, 45), (4, 72), (1, 77),
                                   (1, 64)],
    "fresh_prompt_of_one_token": [(1, 1)],
}
# (spans, index heads, table entries a group: None the kernel's own, all 12
# here). At 4 entries a pair walks at most 3 groups, fewer than the walk
# keeps in flight; at ONE a pair of 8 to 12 groups also runs the walk's
# branch-free loop, the old body (4 heads, a chunk) and the new one alike
INDEX_CASES = [(n, HI, None) for n in sorted(SPANS)] + [
    (n, hi, pages) for n in sorted(ONE_TOKEN_SPANS) for hi in (16, 32)
    for pages in (4, 1)] + [("chunk_behind_decode_rows", HI, 1)]


def _case(name, seed=0, pad=3, hi=HI, spans=None, mb=MB, nh=NH):
    rng = np.random.RandomState(seed)
    spans = spans or SPANS.get(name) or ONE_TOKEN_SPANS[name]
    qlen = np.array([q for q, _ in spans], np.int32)
    kvlen = np.array([k for _, k in spans], np.int32)
    R = len(qlen)
    nb = R * mb + 2
    tables = rng.permutation(nb)[:R * mb].astype(np.int32).reshape(R, mb)
    live = np.zeros((nb, BS), bool)
    for r, k in enumerate(kvlen):
        for b in range(-(-int(k) // BS)):
            live[tables[r, b], :min(BS, int(k) - b * BS)] = True
        tables[r, -(-int(k) // BS):] = nb          # unmapped: the sentinel
    pool = rng.randn(2, nb, BS, W).astype(np.float32)
    pool[..., RANK + ROPE:] = 0.0
    ipool = rng.randn(2, nb, BS, D).astype(np.float32)
    pool[:, ~live] = np.nan
    ipool[:, ~live] = np.nan
    qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).astype(np.int32)
    T = int(qlen.sum()) + pad
    f = jnp.float32
    return dict(
        T=T, live=int(qlen.sum()), pool=jnp.asarray(pool, f),
        ipool=jnp.asarray(ipool, f),
        span=tuple(jnp.asarray(x) for x in (tables, qstart, qlen, kvlen)),
        q_i=jnp.asarray(rng.randn(T, hi, D), f),
        w_i=jnp.asarray(rng.randn(T, hi), f),
        q_nope=jnp.asarray(rng.randn(T, nh, NOPE), f),
        q_pe=jnp.asarray(rng.randn(T, nh, ROPE), f),
        w_kvb=jnp.asarray(rng.randn(RANK, nh * (NOPE + VD)) * RANK ** -0.5,
                          f))


@functools.lru_cache(maxsize=None)
def _scored(name, hi=HI, pages=None):
    """(case, kernel scores, oracle scores, the selection, the kernel's
    operands) of a case, each computed once a module."""
    c = _case(name, hi=hi)
    args = (c["q_i"], c["w_i"], c["ipool"]) + c["span"]
    pages = {} if pages is None else {"pages": pages}
    kernel = jax.jit(functools.partial(dsa.dsa_index_scores_pallas,
                                       layer=1, **pages))
    want = jax.jit(functools.partial(dsa.dsa_index_scores_reference,
                                     layer=1))(*args)
    call, = (e for e in kernel.trace(*args).jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call")
    return (c, np.asarray(kernel(*args)), np.asarray(want),
            jax.jit(lambda s: dsa.dsa_select(s, TOPK))(want),
            len(call.invars))


@pytest.mark.parametrize("name,hi,pages", INDEX_CASES)
def test_index_scores_kernel_equals_oracle(name, hi, pages):
    c, got, want, _, operands = _scored(name, hi, pages)
    assert got.shape == want.shape == (c["T"], MB * BS)
    # a span of one token takes its own path where the tiling says so (16
    # and 32 index heads: the kernel then takes the weights a second time,
    # heads along sublanes), and not at this module's 4 heads
    one_token = dsa.index_grid_params(hi, c["T"])["one_token"]
    assert one_token == (hi >= 16)
    assert operands == 9 + 3 + one_token
    seen = want > 0.5 * NEG_INF
    # the same keys are scored: the row's positions up to the query's own
    assert ((got > 0.5 * NEG_INF) == seen).all()
    _, qstart, qlen, kvlen = (np.asarray(x) for x in c["span"])
    for qs, ql, kl in zip(qstart, qlen, kvlen):
        for i in range(ql):
            assert seen[qs + i].sum() == kl - ql + i + 1
    assert not seen[c["live"]:].any()       # rows outside every span
    assert np.isfinite(got[seen]).all()
    assert np.abs(got[seen] - want[seen]).max() <= 1e-4 * np.abs(
        want[seen]).max()
    # and select the oracle's set
    assert (np.asarray(dsa.dsa_select(jnp.asarray(got), TOPK))
            == np.asarray(dsa.dsa_select_reference(want, TOPK))).all()


@pytest.mark.parametrize("name", sorted(SPANS))
def test_selection_is_top_k_as_a_set(name):
    _, _, want, mask, _ = _scored(name)
    mask = np.asarray(mask)
    assert (mask == np.asarray(dsa.dsa_select_reference(want, TOPK))).all()
    n_seen = (want > 0.5 * NEG_INF).sum(-1)
    assert (mask.sum(-1) == np.minimum(n_seen, TOPK)).all()


@pytest.mark.parametrize("k", [1, 3, 8, 40])
def test_selection_ties_go_to_the_lowest_positions(k):
    """Scores with many exact ties, at the threshold and away from it,
    negative values, zeros of both signs and masked entries."""
    rng = np.random.RandomState(k)
    s = rng.choice([-2.0, -0.0, 0.0, 0.5, 0.5, 1.25, 3.0], (9, 64))
    s[:, 50:] = NEG_INF                         # unseen positions
    s[3, 2:] = NEG_INF                          # fewer seen than k
    s[4] = NEG_INF                              # a dead token
    s[5, :50] = 0.5                             # all tied
    s = jnp.asarray(s, jnp.float32)
    got = np.asarray(dsa.dsa_select(s, k))
    assert (got == np.asarray(dsa.dsa_select_reference(s, k))).all()
    assert got[5, :min(k, 50)].all() and not got[5, min(k, 50):].any()
    assert not got[4].any() and got[3].sum() == min(k, 2)


@functools.lru_cache(maxsize=None)
def _attended(name):
    c, _, _, mask, _ = _scored(name)
    span = dict(scale=(NOPE + ROPE) ** -0.5, layer=1)
    w = c["w_kvb"].reshape(RANK, NH, NOPE + VD)
    q_lat = jnp.einsum("thd,rhd->thr", c["q_nope"], w[..., :NOPE])

    @jax.jit
    def walked(q_lat, q_pe, pool, mask, *sp):
        bias = dsa.selection_bias(mask, NH, table_entries=MB, block_size=BS)
        return dsa.dsa_attention_pallas(q_lat, q_pe, pool, *sp, bias, **span)

    walk = walked(q_lat, c["q_pe"], c["pool"], mask, *c["span"])
    want = jax.jit(lambda qn, qp, w, p, m, *sp: dsa.dsa_attention_reference(
        qn, qp, w, p, *sp, m, k=TOPK, **span))(
        c["q_nope"], c["q_pe"], c["w_kvb"], c["pool"], mask, *c["span"])
    got = jnp.einsum("thr,rhd->thd", walk, w[..., NOPE:])
    return c, np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("name", sorted(SPANS))
def test_attention_over_the_selection_equals_oracle(name):
    """Absorbed form (the kernel's walk with the selection as a mask) against
    the expanded oracle over gathered rows, over a pool that is NaN wherever
    no live row may read."""
    c, got, want = _attended(name)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    assert not got[c["live"]:].any()        # rows outside every span: zeros


EDGE_HEADS = 16


@functools.lru_cache(maxsize=None)
def _edge_attended(path):
    """ONE call of the attention over a selection, 16 heads, over
    ``test_mla_attention.edge_spans``: a row for each group count around the
    edges of the walk's pipeline (``_walk_ahead`` at the latent kernel's
    ``SLOTS``), ending inside its last group (the rest of it NaN), and one
    ending on a group's edge. ``path`` "one_token": decode rows; "span":
    spans of three tokens. A query selects about half the keys it sees, so
    some groups of a walk hold none of them. Returns (case, kernel, oracle,
    the rows' (start, span, kv length))."""
    pages, mb = edges.EDGE_PAGES, edges.EDGE_ENTRIES
    c = _case(path, seed=len(path), mb=mb, nh=EDGE_HEADS,
              spans=edges.edge_spans({"one_token": 1, "span": 3}[path]))
    _, qstart, qlen, kvlen = (np.asarray(x) for x in c["span"])
    rng = np.random.RandomState(7)
    mask = np.zeros((c["T"], mb * BS), bool)
    for qs, ql, kl in zip(qstart, qlen, kvlen):
        for i in range(ql):
            seen = kl - ql + i + 1
            mask[qs + i, :seen] = rng.rand(seen) < 0.5
            mask[qs + i, rng.randint(seen)] = True
    mask = jnp.asarray(mask)
    k = int(mask.sum(-1).max())
    span = dict(scale=(NOPE + ROPE) ** -0.5, layer=1)
    w = c["w_kvb"].reshape(RANK, EDGE_HEADS, NOPE + VD)
    q_lat = jnp.einsum("thd,rhd->thr", c["q_nope"], w[..., :NOPE])

    @jax.jit
    def walked(q_lat, q_pe, pool, mask, *sp):
        bias = dsa.selection_bias(mask, EDGE_HEADS, pages=pages,
                                  table_entries=mb, block_size=BS)
        return dsa.dsa_attention_pallas(q_lat, q_pe, pool, *sp, bias,
                                        pages=pages, **span)

    walk = walked(q_lat, c["q_pe"], c["pool"], mask, *c["span"])
    want = jax.jit(lambda qn, qp, w, p, m, *sp: dsa.dsa_attention_reference(
        qn, qp, w, p, *sp, m, k=k, **span))(
        c["q_nope"], c["q_pe"], c["w_kvb"], c["pool"], mask, *c["span"])
    got = jnp.einsum("thr,rhd->thd", walk, w[..., NOPE:])
    return c, np.asarray(got), np.asarray(want), list(zip(qstart, qlen,
                                                          kvlen))


@pytest.mark.parametrize("path", ["one_token", "span"])
@pytest.mark.parametrize("case", edges.EDGE_CASES)
def test_attention_over_the_selection_at_the_pipelines_edges(case, path):
    """The walk the index kernel shares, under the latent kernel with a
    selection: each row of ``_edge_attended`` against the oracle over a
    NaN-poisoned pool (a slot read before its copy landed, or a group of
    another pair's, shows as NaN or a miss)."""
    c, got, want, rows = _edge_attended(path)
    assert np.isfinite(got).all()
    qs, ql, kl = rows[edges.EDGE_CASES.index(case)]
    if case == 0:
        assert (ql, kl) == (0, 0)               # the dead row: no pair
        assert not got[c["live"]:].any()
        return
    assert -(-kl // (edges.EDGE_PAGES * BS)) == edges.edge_groups(case)
    assert np.abs(got[qs:qs + ql] - want[qs:qs + ql]).max() \
        <= 1e-4 * np.abs(want).max()
    assert np.abs(got[qs:qs + ql]).min() > 0


def test_the_selection_binds():
    """The oracle over the selection is not the dense latent attention: a
    dropped mask would fail the comparison above."""
    from paddle_tpu.kernels.pallas_mla_ragged_attention import \
        mla_ragged_attention_reference
    c, walk, _ = _attended("decode_rows")
    dense = np.asarray(jax.jit(functools.partial(
        mla_ragged_attention_reference, scale=(NOPE + ROPE) ** -0.5,
        layer=1))(c["q_nope"], c["q_pe"], c["w_kvb"], c["pool"],
                  *c["span"]))
    rows = np.asarray(c["span"][3]) > TOPK      # contexts longer than k
    assert np.abs(walk[:5][rows] - dense[:5][rows]).max() \
        > 0.05 * np.abs(dense).max()
    assert np.abs(walk[:5][~rows] - dense[:5][~rows]).max() \
        <= 1e-4 * np.abs(dense).max()


# ------------------------------------------------- the router with a bias
E_ALL, TOP = 16, 4


def _router_case(seed=3, rows=40, hid=24):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(rows, hid), jnp.float32)
    router = jnp.asarray(rng.randn(hid, E_ALL) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.randn(E_ALL) * 0.2, jnp.float32)
    return h, router, bias


@pytest.mark.parametrize("first_held,n_held", [(0, 16), (4, 4), (12, 4)])
def test_route_with_bias_equals_reference(first_held, n_held):
    """Bias in the selection, not in the weights; the weights' sum over all
    picks, whatever range is held."""
    h, router, bias = _router_case()
    w, picks, loc, counts, stats = moe_mod._route(
        h, router, TOP, jnp.ones(h.shape[0], bool), True, n_held,
        first_held=first_held, scale=2.5, router_bias=bias)
    scores = jax.nn.sigmoid(h @ router)
    hy = dict(top_k=TOP, norm_topk_prob=True, routed_scale=2.5)
    want_e, want_w = ref.route(scores, bias,
                               jnp.full((h.shape[0], TOP), -1), hy)
    assert (np.sort(picks, -1) == np.sort(want_e, -1)).all()
    order = np.argsort(picks, -1), np.argsort(np.asarray(want_e), -1)
    assert np.allclose(np.take_along_axis(np.asarray(w), order[0], -1),
                       np.take_along_axis(np.asarray(want_w), order[1], -1),
                       rtol=1e-5)
    assert np.allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    held = (np.asarray(picks) >= first_held) \
        & (np.asarray(picks) < first_held + n_held)
    assert int(stats[0]) == held.sum() == int(counts.sum())
    assert (np.asarray(loc)[held] == np.asarray(picks)[held]
            - first_held).all()
    # the bias decides: without it some row picks another set, and a bias
    # left in the weights would move their sum
    _, plain, *_ = moe_mod._route(
        h, router, TOP, jnp.ones(h.shape[0], bool), True, n_held,
        first_held=first_held, scale=2.5,
        router_bias=jnp.zeros_like(bias))
    assert (np.sort(plain, -1) != np.sort(picks, -1)).any()


def test_softmax_router_is_unchanged_by_the_new_argument():
    h, router, _ = _router_case()
    live = jnp.ones(h.shape[0], bool)
    a = moe_mod._route(h, router, TOP, live, False, E_ALL)
    b = moe_mod._route(h, router, TOP, live, False, E_ALL, router_bias=None)
    for x, y in zip(a, b):
        assert (np.asarray(x) == np.asarray(y)).all()
    probs = jax.nn.softmax(h @ router, -1)
    assert np.allclose(a[0], jax.lax.top_k(probs, TOP)[0], rtol=1e-6)


#: sha256[:16] of the lowered text of DeepSeek-V2-tiny's unified step and
#: whole-prompt prefill, by attention path: PR 42's tree (a450a4c), but the
#: "pallas" step, which is PR 45's tree's (PR 42's was 8e267d88bc2173d4), and
#: all four recorded again in PR 57, whose routed FFN returns a fifth stat
#: and builds the buffer of a chip's share of the pairs (before it: steps
#: 20b9ff0e2b430953 / 0e9bb0b2f4733e93, prefill 96dc5e47a4e1614a)
PR42_PROGRAMS_PR45_KERNEL = {
    "jnp": ("964a99292e901700", "73034f58a016e0ec"),
    "pallas": ("a02081de10f46fe2", "73034f58a016e0ec")}


@pytest.mark.parametrize("attention", sorted(PR42_PROGRAMS_PR45_KERNEL))
def test_a_tree_with_no_indexer_runs_the_programs_it_ran(attention):
    """A tree without ``idx_layer`` (DeepSeek-V2): its unified step and its
    prefill lower to the TEXT they lowered to before this model came: the
    same ops on the same shapes in the same order, so the same bits. The
    hashes are of this container's jax; another jax re-records them. The
    "pallas" step was recorded again in PR 45, which changed the latent
    kernel's walk (``_walk_ahead`` in place of the two-slot walk, the
    values past ``kvlen`` zeroed by a select in every group in place of a
    ``lax.cond``) and nothing else of the step: the "jnp" step, which runs
    everything but that kernel, and both prefills still match PR 42's.
    PR 57 changed the routed FFN of all four (``kernels/moe_ffn.py``: the
    pair buffer under a held range, ``STATS``' fifth entry) and nothing of
    attention: recorded again, the two steps still differing by the kernel
    alone and the two prefills still one text."""
    if jax.__version__ != "0.9.0":
        pytest.skip("the recorded texts are jax 0.9.0's")
    paddle.seed(11)
    m = DeepseekV2ForCausalLM(deepseek_v2_tiny(decode_attention=attention))
    c = m.config
    params, tied = m.decode_params()
    consts = dict(nh=c.num_attention_heads, nkv=c.num_key_value_heads,
                  hd=c.head_dim, eps=float(c.rms_norm_eps),
                  theta=float(c.rope_theta), tied=tied, moe=c.routing,
                  mla=c.mla, return_picks=True)
    R, T, nb, bs, mb = 4, 40, 12, 32, 3
    W = decode_mod.latent_row_width(c.kv_lora_rank, c.qk_rope_head_dim)
    i32, u32 = jnp.int32, jnp.uint32

    def z(*s):
        return jnp.zeros(s, i32)

    step = decode_mod.build_ragged_step_fn(
        n_steps=1, decode_attn=attention, donate=False, **consts)
    text = step.lower(
        params, jnp.zeros((c.num_hidden_layers, nb, bs, W)),
        jnp.zeros((c.num_hidden_layers, nb, bs, 0)), z(R, mb), z(T), z(T),
        z(T), z(R), z(R), z(R), z(R), jnp.zeros((R, 2), u32), jnp.zeros(R),
        z(R), z(R), z(R), jnp.zeros((R, 2), u32), z(R)).as_text()
    prefill = decode_mod.build_prefill_fn(**consts).lower(
        params, z(2, 16), z(2), jnp.zeros((2, 2), u32), jnp.zeros(2),
        z(2)).as_text()
    assert "dsa_" not in text and "dsa_" not in prefill
    got = tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in (text, prefill))
    assert got == PR42_PROGRAMS_PR45_KERNEL[attention]
