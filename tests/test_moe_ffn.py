"""The dropless routed FFN against its plain oracle (ISSUE 26, tests (a)):
``moe_ffn`` (pairs ordered by expert, grouped matmuls) equals
``moe_ffn_reference`` (every expert over every row, masked) under uniform
routing, under routing forced onto one expert set, and with dead rows
interleaved; the routing summary counts only live pairs. Since ISSUE 57 the
same under a held range, whose buffer holds the pairs the held experts take
(``_capacity``), whose overflow takes it in more passes than one, and whose
way back to the rows is a product over the slots or, for a buffer over
``PRODUCT_SLOTS``, the gather by pair."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import moe_ffn as moe_ffn_module
from paddle_tpu.kernels.moe_ffn import (PAIR_TILE, STATS, _capacity, moe_ffn,
                                        moe_ffn_reference)

T, H, E, I, K = 37, 64, 8, 32, 2


def _weights(seed, router_scale=1.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (T, H)),
            jax.random.normal(k[1], (H, E)) * router_scale,
            jax.random.normal(k[2], (E, H, I)) * 0.1,
            jax.random.normal(k[3], (E, H, I)) * 0.1,
            jax.random.normal(k[4], (E, I, H)) * 0.1)


def _forced_router(experts):
    """A router that sends every token to ``experts``, whatever it holds:
    zero weights would tie, so a constant column bias is folded in through
    a hidden unit that every test input shares."""
    r = np.zeros((H, E), np.float32)
    r[0, list(experts)] = 50.0
    return jnp.asarray(r)


def _live(kind):
    if kind == "all":
        return None
    if kind == "interleaved":
        return jnp.arange(T) % 3 != 1
    if kind == "one":
        return jnp.arange(T) == 5
    return jnp.zeros(T, bool)               # "none"


@pytest.mark.parametrize("live_kind", ["all", "interleaved", "one", "none"])
@pytest.mark.parametrize("routing", ["uniform", "forced"])
def test_moe_ffn_equals_reference(routing, live_kind):
    h, r, wg, wu, wd = _weights(0)
    if routing == "forced":
        # every token on the same K experts: the skew a dropless FFN must
        # take without a capacity
        h = h.at[:, 0].set(1.0)
        r = _forced_router((2, 5))
    live = _live(live_kind)
    out, stats = jax.jit(lambda *a: moe_ffn(*a, top_k=K, live=live))(
        h, r, wg, wu, wd)
    ref, ref_stats = jax.jit(
        lambda *a: moe_ffn_reference(*a, top_k=K, live=live))(
        h, r, wg, wu, wd)
    scale = max(float(jnp.max(jnp.abs(ref))), 1e-6)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * scale
    n_live = T if live is None else int(jnp.sum(live))
    stats, ref_stats = np.asarray(stats), np.asarray(ref_stats)
    assert (stats == ref_stats).all()
    pairs, touched, fullest, picks, compact = (int(stats[STATS.index(n)])
                                               for n in STATS)
    # dropless: K per live row; every expert held, so every pick a pair
    # and the buffer every pick's
    assert pairs == picks == n_live * K and compact == 0
    if routing == "forced" and n_live:
        assert touched == K and fullest == n_live
    if not n_live:
        assert touched == 0 and float(jnp.max(jnp.abs(out))) == 0.0
    if live is not None:                        # a dead row gets no FFN
        assert float(jnp.max(jnp.abs(jnp.where(live[:, None], 0, out)))) == 0


def test_renormalize_divides_the_picked_weights_by_their_sum():
    h, r, wg, wu, wd = _weights(1, router_scale=0.05)
    raw, _ = moe_ffn(h, r, wg, wu, wd, top_k=K)
    ren, _ = moe_ffn(h, r, wg, wu, wd, top_k=K, renormalize=True)
    p = jax.nn.softmax(h @ r, -1)
    top = jnp.sum(jax.lax.top_k(p, K)[0], -1, keepdims=True)
    assert float(jnp.max(jnp.abs(ren * top - raw))) <= 1e-5
    assert float(jnp.max(jnp.abs(ren - raw))) > 1e-2   # and it matters


def test_leading_dims_and_one_program_for_every_routing():
    h, r, wg, wu, wd = _weights(2)
    f = jax.jit(lambda h, r, live: moe_ffn(h, r, wg, wu, wd, top_k=K,
                                           live=live))
    h3 = h[:36].reshape(3, 12, H)
    live = jnp.ones((3, 12), bool)
    out, _ = f(h3, r, live)
    assert out.shape == h3.shape
    f(h3, _forced_router((0, 1)), live.at[0].set(False))
    assert f._cache_size() == 1


def test_a_layer_of_a_stack_is_read_in_place():
    """What a layer scan hands the FFN: the expert weights stacked over
    layers and the layer's index; the result is that layer's."""
    h, r, wg, wu, wd = _weights(3)
    stacks = [jnp.stack([0 * w, w, 2 * w]) for w in (wg, wu, wd)]
    f = jax.jit(lambda layer: moe_ffn(h, r, *stacks, top_k=K, layer=layer))
    want, stats = moe_ffn(h, r, wg, wu, wd, top_k=K)
    got, got_stats = f(jnp.int32(1))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6
    assert (np.asarray(stats) == np.asarray(got_stats)).all()
    assert float(jnp.max(jnp.abs(f(jnp.int32(0))[0]))) == 0.0
    assert f._cache_size() == 1


# ---- a held range: this chip's share of a wider router (ISSUE 57) ----------
#: (router width, first held id, held experts); four picks a row make 148
#: pairs = 256 slots, of which the held experts' buffer is one tile
HELD = {"2of8": (8, 2, 2), "4of16": (16, 4, 4)}
HK = 4


@functools.lru_cache(maxsize=None)
def _held_programs(held, variant, back="product"):
    """(inputs, jitted moe_ffn, jitted reference) of one held range; h,
    router and live are arguments, so the routings share a program. ``back``
    names the way back from the slots the program is to be traced with (a
    test's ``_way_back``): a program of its own for each."""
    n_exp, first, n_held = HELD[held]
    k = jax.random.split(jax.random.PRNGKey(n_exp), 6)
    h = jax.random.normal(k[0], (T, H))
    router = jax.random.normal(k[1], (H, n_exp))
    wg, wu, wd = (jax.random.normal(k[2], (n_held, H, I)) * 0.1,
                  jax.random.normal(k[3], (n_held, H, I)) * 0.1,
                  jax.random.normal(k[4], (n_held, I, H)) * 0.1)
    kw = dict(top_k=HK, first_held=first)
    fn, ref = moe_ffn, moe_ffn_reference
    if variant == "layer":                  # the stack read in place
        stacks = [jnp.stack([0 * w, w, 2 * w]) for w in (wg, wu, wd)]
        fn = functools.partial(moe_ffn, layer=jnp.int32(1))
        args, ref_args = stacks, (wg, wu, wd)
    elif variant == "two_matrix":
        args = ref_args = (None, jnp.swapaxes(wu, 1, 2), wd)
    else:
        args = ref_args = (wg, wu, wd)
    if variant == "bias_renormalize":       # the weights over ALL picks
        kw.update(router_bias=jax.random.normal(k[5], (n_exp,)) * 0.1,
                  renormalize=True, scale=2.5)
    return (h, router), jax.jit(
        lambda h, r, live: fn(h, r, *args, live=live, **kw)), jax.jit(
        lambda h, r, live: ref(h, r, *ref_args, live=live, **kw))


def _two_kinds_of_row(held, n_every, n_one):
    """Inputs that put ``HK`` pairs a row on held experts for the first
    ``n_every`` rows (``n_held`` is ``HK``) and one pair a row for the next
    ``n_one``; the rows after them are dead."""
    n_exp, first, n_held = HELD[held]
    assert n_held == HK
    (h, _), *_ = _held_programs(held, "plain")
    h = h.at[:, :2].set(0.0).at[:n_every, 0].set(1.0).at[
        n_every:n_every + n_one, 1].set(1.0)
    r = np.zeros((H, n_exp), np.float32)
    r[0, first:first + HK] = 50.0
    r[1, [first] + [e for e in range(n_exp)
                    if not first <= e < first + n_held][:HK - 1]] = 50.0
    return h, jnp.asarray(r), jnp.arange(T) < n_every + n_one


def _way_back(monkeypatch, back):
    """The buffer of these sizes is one tile, far under ``PRODUCT_SLOTS``:
    the gather by pair, a whole-prompt program's way back, is reached by
    lowering the bar."""
    monkeypatch.setattr(moe_ffn_module, "PRODUCT_SLOTS",
                        {"product": PAIR_TILE, "gather": 0}[back])


CAP = _capacity(T * HK, 4, 16)
HELD_CASES = [(held, case) for held in HELD for case in (
    "uniform", "interleaved", "none", "forced", "layer", "two_matrix",
    "bias_renormalize")] + [("4of16", "exactly_cap"), ("4of16", "cap_plus_1")]


@pytest.mark.parametrize("back", ["product", "gather"])
@pytest.mark.parametrize("held,case", HELD_CASES)
def test_a_held_range_equals_reference(held, case, back, monkeypatch):
    _way_back(monkeypatch, back)
    n_exp, first, n_held = HELD[held]
    variant = case if case in ("layer", "two_matrix", "bias_renormalize") \
        else "plain"
    (h, r), fn, ref = _held_programs(held, variant, back)
    live = jnp.ones(T, bool)
    if case in ("interleaved", "none"):
        live = _live(case)
    elif case == "forced":
        # every row on the same HK experts, the held ones first: with 4 held
        # every pick is a pair and the held pairs take two passes
        h = h.at[:, 0].set(1.0)
        forced = np.zeros((H, n_exp), np.float32)
        forced[0, first:first + HK] = 50.0
        r = jnp.asarray(forced)
    elif case == "exactly_cap":
        h, r, live = _two_kinds_of_row(held, CAP // HK, 0)
    elif case == "cap_plus_1":
        h, r, live = _two_kinds_of_row(held, CAP // HK, 1)
    out, stats = fn(h, r, live)
    want, want_stats = ref(h, r, live)
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    assert float(jnp.max(jnp.abs(out - want))) <= 1e-5 * scale
    stats = np.asarray(stats)
    assert (stats == np.asarray(want_stats)).all()
    pairs, _, _, picks, compact = (int(v) for v in stats)
    cap = _capacity(T * HK, n_held, n_exp)
    assert cap == PAIR_TILE                    # the smaller buffer exists
    assert picks == int(jnp.sum(live)) * HK and pairs <= picks
    assert compact == (pairs <= cap)           # one pass, or more
    want_pairs = {"none": 0, "exactly_cap": cap, "cap_plus_1": cap + 1,
                  "forced": T * min(HK, n_held)}.get(case)
    if want_pairs is not None:
        assert pairs == want_pairs
    if case in ("uniform", "interleaved"):
        assert 0 < pairs < picks


def test_with_every_expert_held_the_program_is_the_straight_line():
    """The control (OLMoE) traces no branch and no loop; a held range whose
    buffer is smaller traces the loop of passes that keeps it dropless."""
    h, r, wg, wu, wd = _weights(4)
    assert _capacity(T * HK, E, E) is None
    whole = str(jax.make_jaxpr(
        lambda *a: moe_ffn(*a, top_k=HK))(h, r, wg, wu, wd))
    assert "cond[" not in whole and "while[" not in whole     # primitives
    share = str(jax.make_jaxpr(lambda *a: moe_ffn(*a, top_k=HK, first_held=2))(
        h, r, wg[:2], wu[:2], wd[:2]))
    assert "while[" in share and "cond[" not in share


@pytest.mark.parametrize("back", ["every_pick", "product", "gather"])
def test_a_row_that_is_not_finite_stays_in_its_row(back, monkeypatch):
    """One request's activations overflow: its row of the step comes out not
    finite, as the reference's does, and every other row as if it had not
    been there (a product over the slots would hand 0 x inf to them all)."""
    if back == "every_pick":
        h, r, *ws = _weights(5)
        fn, ref = (jax.jit(lambda h: f(h, r, *ws, top_k=K)[0])
                   for f in (moe_ffn, moe_ffn_reference))
        row = 3
    else:
        _way_back(monkeypatch, back)
        (h, r), fn, ref = _held_programs("4of16", "plain", back)
        fn, ref = (functools.partial(
            lambda f, h: f(h, r, jnp.ones(T, bool))[0], f) for f in (fn, ref))
        # a row whose first pick is held (ids 4..7): scaled up it keeps it
        first = np.argmax(h @ r, axis=1)
        row = int(np.flatnonzero((first >= 4) & (first < 8))[0])
    h = h.at[row].multiply(1e30)            # silu(g) * u overflows float32
    out, want = np.asarray(fn(h)), np.asarray(ref(h))
    bad = ~np.isfinite(want).all(axis=1)
    assert bad[row] and bad.sum() == 1
    assert (~np.isfinite(out).all(axis=1) == bad).all()
    assert np.abs(out[~bad] - want[~bad]).max() <= 1e-5 * np.abs(
        want[~bad]).max()


def test_a_whole_prompt_program_goes_back_by_the_gather():
    """At 8,192 rows of Qwen3-Next's shapes (``engine.WHOLE_PROMPT_ROWS``; 64
    of 512 held, ten picks) the buffer is 20,480 slots: over
    ``PRODUCT_SLOTS``, so the program holds no ``[rows, slots]`` matrix of
    weights (671 MB of float32 and 2 TFLOP a layer call there), where the
    decode step's 384 slots do."""
    def traced(rows):
        shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
            (rows, 2048), (2048, 512), (64, 2048, 512), (64, 2048, 512),
            (64, 512, 2048))]
        return str(jax.make_jaxpr(lambda *a: moe_ffn(
            *a, top_k=10, renormalize=True, first_held=0))(*shapes))
    for rows, by_product in ((128, True), (8192, False)):
        cap = _capacity(rows * 10, 64, 512)
        assert cap == {128: 384, 8192: 20480}[rows]
        assert (cap <= moe_ffn_module.PRODUCT_SLOTS) == by_product
        assert (f"f32[{rows},{cap}]" in traced(rows)) == by_product
