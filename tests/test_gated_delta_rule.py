"""The gated delta rule's three implementations (``kernels.gated_delta_rule``)
against the token-by-token recurrence: the chunked ``jax.numpy`` form and the
two Pallas kernels (interpret mode on the CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import gated_delta_rule as gdr

H, DK, DV = 4, 24, 48
R = 5


def _inputs(seed, T, g_lo=-1.6, g_hi=0.0, beta_lo=0.0, beta_hi=2.0,
            widths=(H, DK, DV), slots=R):
    """A packed buffer of ``T`` tokens and a store of two layers (in the
    store's own layout, ``gdr.state_shape``)."""
    nh, dk, dv = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdr.l2norm(jax.random.normal(ks[0], (T, nh, dk)), dk ** -0.5)
    k = gdr.l2norm(jax.random.normal(ks[1], (T, nh, dk)))
    v = jax.random.normal(ks[2], (T, nh, dv))
    g = jax.random.uniform(ks[3], (T, nh), minval=g_lo, maxval=g_hi)
    beta = jax.random.uniform(ks[4], (T, nh), minval=beta_lo, maxval=beta_hi)
    state = jax.random.normal(ks[5], (2, slots) + gdr.state_shape(*widths))
    return q, k, v, g, beta, state


def _oracle(q, k, v, g, beta, state, layer, start, length, fresh):
    """Every span through ``gdn_recurrence``, by hand, on head-major states;
    the store is read and written through the layout's two helpers."""
    o = np.zeros(v.shape, np.float32)
    heads = np.array(gdr.state_from_store(state, q.shape[1]))
    for r in range(len(start)):
        if length[r] == 0:
            continue
        sl = slice(start[r], start[r] + length[r])
        s0 = None if fresh[r] else jnp.asarray(heads[layer, r])
        o_r, s_r = gdr.gdn_recurrence(q[sl], k[sl], v[sl], g[sl], beta[sl],
                                      s0)
        o[sl] = np.asarray(o_r)
        heads[layer, r] = np.asarray(s_r)
    return o, np.asarray(gdr.state_to_store(jnp.asarray(heads)))


def _close(got, want, tol=2e-4):
    want = np.asarray(want)
    if not want.size:
        return
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * scale


SPANS = {
    # two spans that share a block of the packed buffer, one fresh, and a
    # slot with nothing
    "shared_block": ([3, 0, 40, 0, 0], [37, 0, 100, 0, 0], [0, 0, 1, 0, 0]),
    # lengths that are no multiple of the chunk, a span inside one block
    "odd": ([0, 70, 0, 75, 0], [70, 5, 0, 129, 0], [1, 0, 0, 0, 0]),
    # one long span from a carried state
    "long": ([0, 0, 0, 0, 10], [0, 0, 0, 0, 190], [0, 0, 0, 0, 0]),
    # no span at all: the store comes back as it was
    "none": ([0] * 5, [0] * 5, [0] * 5),
}


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(SPANS))
def test_chunk_scan_matches_recurrence(impl, case):
    start, length, fresh = (np.asarray(x) for x in SPANS[case])
    T = 210
    q, k, v, g, beta, state = _inputs(1, T)
    fn = gdr.gdn_chunk_scan if impl == "pallas" else gdr.gdn_chunk_scan_jnp
    o, st = fn(q, k, v, g, beta, state, layer=1, start=start, length=length,
               fresh=fresh)
    want_o, want_st = _oracle(q, k, v, g, beta, state, 1, start, length,
                              fresh)
    live = np.zeros(T, bool)
    for s, n in zip(start, length):
        live[s:s + n] = True
    _close(np.asarray(o)[live], want_o[live])
    _close(st, want_st)
    # the other layer and the slots without a span are untouched, bit for bit
    assert np.array_equal(np.asarray(st)[0], np.asarray(state)[0])
    idle = length == 0
    assert np.array_equal(np.asarray(st)[1, idle], np.asarray(state)[1, idle])


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("name,kw", [
    ("steep_decay", dict(g_lo=-1.6, g_hi=-1.6)),
    ("beta_near_0", dict(beta_lo=0.0, beta_hi=0.02)),
    ("beta_near_2", dict(beta_lo=1.95, beta_hi=2.0)),
    ("no_decay", dict(g_lo=0.0, g_hi=0.0)),
])
def test_chunk_scan_edges(impl, name, kw):
    """``g`` = -1.6 through whole chunks stays finite; ``beta`` at both ends."""
    T = 128
    q, k, v, g, beta, state = _inputs(2, T, **kw)
    start, length, fresh = [0] * 5, [T, 0, 0, 0, 0], [0] * 5
    fn = gdr.gdn_chunk_scan if impl == "pallas" else gdr.gdn_chunk_scan_jnp
    o, st = fn(q, k, v, g, beta, state, layer=0, start=start, length=length,
               fresh=fresh)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(st).all())
    want_o, want_st = _oracle(q, k, v, g, beta, state, 0, start, length,
                              fresh)
    _close(o, want_o, 5e-4)
    _close(st, want_st, 5e-4)


def test_two_chunks_equal_one_pass():
    """A span cut in two calls, the state carried by the store, equals one."""
    T = 150
    q, k, v, g, beta, state = _inputs(3, T)
    one_o, one_st = gdr.gdn_chunk_scan(
        q, k, v, g, beta, state, layer=0, start=[0] * 5,
        length=[0, 0, T, 0, 0], fresh=[0, 0, 1, 0, 0])
    o1, st1 = gdr.gdn_chunk_scan(
        q[:96], k[:96], v[:96], g[:96], beta[:96], state, layer=0,
        start=[0] * 5, length=[0, 0, 96, 0, 0], fresh=[0, 0, 1, 0, 0])
    o2, st2 = gdr.gdn_chunk_scan(
        q[96:], k[96:], v[96:], g[96:], beta[96:], st1, layer=0,
        start=[0] * 5, length=[0, 0, T - 96, 0, 0], fresh=[0] * 5)
    _close(jnp.concatenate([o1, o2]), np.asarray(one_o))
    _close(st2, np.asarray(one_st))


def test_padding_rows_leave_state_alone():
    """Rows with ``beta`` 0 and ``g`` 0 behind a span's end do nothing."""
    T = 96
    q, k, v, g, beta, state = _inputs(4, T)
    pad = jnp.arange(T) >= 50
    g = jnp.where(pad[:, None], 0.0, g)
    beta = jnp.where(pad[:, None], 0.0, beta)
    _, full = gdr.gdn_chunk_scan_jnp(
        q, k, v, g, beta, state, layer=0, start=[0] * 5,
        length=[T, 0, 0, 0, 0], fresh=[0] * 5)
    _, cut = gdr.gdn_chunk_scan_jnp(
        q, k, v, g, beta, state, layer=0, start=[0] * 5,
        length=[50, 0, 0, 0, 0], fresh=[0] * 5)
    _close(full, np.asarray(cut), 1e-5)


@pytest.mark.parametrize("live,fresh", [
    ([1, 0, 1, 1, 0], [0, 0, 1, 0, 0]),
    ([0, 0, 0, 0, 1], [0, 0, 0, 0, 0]),
    ([0] * 5, [0] * 5),
    ([1] * 5, [1] * 5),
])
def test_recurrent_update_matches_recurrence(live, fresh):
    q, k, v, g, beta, state = _inputs(5, R)
    o, st = gdr.gdn_recurrent_update(q, k, v, g, beta, state, layer=1,
                                     live=live, fresh=fresh)
    want_o, want_st = _oracle(q, k, v, g, beta, state, 1, np.arange(R),
                              np.asarray(live), fresh)
    on = np.asarray(live, bool)
    _close(np.asarray(o)[on], want_o[on], 1e-5)
    _close(st, want_st, 1e-5)
    assert np.array_equal(np.asarray(st)[1, ~on], np.asarray(state)[1, ~on])
    assert np.array_equal(np.asarray(st)[0], np.asarray(state)[0])


def test_reference_walks_the_packed_buffer():
    """The oracle over a packed buffer: decode rows, a chunk, dead rows."""
    T = 40
    q, k, v, g, beta, state = _inputs(6, T)
    seg = np.full(T, R, np.int32)
    seg[0], seg[1] = 3, 0                 # two decode rows
    seg[2:32] = 2                         # a fresh chunk
    first = np.zeros(T, bool)
    first[2] = True
    o, st = gdr.gdn_reference(q, k, v, g, beta, state, layer=0, seg=seg,
                              first=first)
    start, length = [1, 0, 2, 0, 0], [1, 0, 30, 1, 0]
    want_o, want_st = _oracle(q, k, v, g, beta, state, 0, start, length,
                              [0, 0, 1, 0, 0])
    _close(np.asarray(o)[:32], want_o[:32], 1e-5)
    _close(st, want_st, 1e-5)


# ---- the store's layout (PR 49): [dk, H dv], every head's values side by
# side on the lanes, whatever the widths
WIDTHS = {
    # the published Olmo-Hybrid widths: a head boundary inside every second
    # lane tile (192 = 1.5 tiles), 45 whole tiles
    "published": (30, 96, 192),
    # an odd head count: the last lane tile is half full (3 x 192 = 4.5)
    "odd_heads": (3, 16, 192),
    # a value width that is whole lane tiles: no tile holds two heads
    "lane_multiple": (2, 8, 128),
    # several heads inside one tile, and less than one tile in all
    "narrow": (4, 8, 16),
}


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_the_layout_helpers_round_trip(widths):
    nh, dk, dv = WIDTHS[widths]
    assert gdr.state_shape(nh, dk, dv) == (dk, nh * dv)
    s = jnp.arange(2 * 3 * nh * dk * dv, dtype=jnp.float32).reshape(
        2, 3, nh, dk, dv)
    st = gdr.state_to_store(s)
    assert st.shape == (2, 3) + gdr.state_shape(nh, dk, dv)
    # head h's value j at key row i lies at lane h dv + j of sublane i
    h, i, j = nh - 1, dk - 2, dv - 3
    assert float(st[1, 2, i, h * dv + j]) == float(s[1, 2, h, i, j])
    assert np.array_equal(np.asarray(gdr.state_from_store(st, nh)),
                          np.asarray(s))


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_recurrent_update_over_the_stored_layout(widths):
    """The update against ``gdn_reference`` at each shape of the layout: a
    dead slot, and a fresh row over a slot that holds ``NaN`` (it starts from
    zero whatever its slot held)."""
    slots = 3
    q, k, v, g, beta, state = _inputs(11, slots, widths=WIDTHS[widths],
                                      slots=slots)
    live, fresh = np.array([1, 0, 1], bool), np.array([0, 0, 1], bool)
    poisoned = state.at[:, 2].set(jnp.nan)
    o, st = gdr.gdn_recurrent_update(q, k, v, g, beta, poisoned, layer=1,
                                     live=live, fresh=fresh)
    want_o, want_st = gdr.gdn_reference(
        q, k, v, g, beta, state, layer=1, seg=np.where(live, np.arange(slots),
                                                       slots), first=fresh)
    assert st.shape == state.shape
    assert bool(jnp.isfinite(o[live]).all()) and bool(jnp.isfinite(
        st[1, live]).all())
    _close(np.asarray(o)[live], np.asarray(want_o)[live], 1e-5)
    _close(np.asarray(st)[1, live], np.asarray(want_st)[1, live], 1e-5)
    assert np.array_equal(np.asarray(st)[1, 1], np.asarray(state)[1, 1])
    assert np.array_equal(np.asarray(st)[0, :2], np.asarray(state)[0, :2])


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_chunk_scan_over_the_stored_layout(impl, widths):
    """The scan against ``gdn_reference`` at each shape of the layout: a span
    that continues its slot's state and ends inside the block where a fresh
    one, over a ``NaN`` slot, starts."""
    slots, T = 3, 100
    q, k, v, g, beta, state = _inputs(12, T, widths=WIDTHS[widths],
                                      slots=slots)
    start, length = np.array([2, 0, 40]), np.array([38, 0, 57])
    fresh = np.array([0, 0, 1], bool)
    seg = np.full(T, slots, np.int32)
    seg[2:40], seg[40:97] = 0, 2
    first = np.zeros(T, bool)
    first[40] = True
    poisoned = state.at[:, 2].set(jnp.nan)
    fn = gdr.gdn_chunk_scan if impl == "pallas" else gdr.gdn_chunk_scan_jnp
    o, st = fn(q, k, v, g, beta, poisoned, layer=0, start=start,
               length=length, fresh=fresh)
    want_o, want_st = gdr.gdn_reference(q, k, v, g, beta, state, layer=0,
                                        seg=seg, first=first)
    assert st.shape == state.shape
    assert bool(jnp.isfinite(o[2:97]).all())
    _close(np.asarray(o)[2:97], np.asarray(want_o)[2:97])
    _close(np.asarray(st)[0, [0, 2]], np.asarray(want_st)[0, [0, 2]])
    assert np.array_equal(np.asarray(st)[0, 1], np.asarray(state)[0, 1])


# ---- grouped key heads (PR 54): q and k at fewer heads than v, value head h
# on key head h // (H / Hk)
GROUPED = {
    # the published Qwen3-Next widths: 32 value heads on 16 key heads, a
    # value head a lane tile
    "published_32_on_16": (32, 16, 128, 128),
    # four value heads a key head, several value heads inside one lane tile
    "narrow_8_on_2": (8, 2, 8, 16),
    # a head boundary inside a lane tile between two KEY heads' value heads
    "split_tile_6_on_3": (6, 3, 16, 192),
}


def _grouped_inputs(seed, T, widths, slots):
    nh, nk, dk, dv = widths
    q, k, v, g, beta, state = _inputs(seed, T, widths=(nh, dk, dv),
                                      slots=slots)
    # key head j is the first of its value heads' draws: any 'nk' will do
    return q[:, ::nh // nk], k[:, ::nh // nk], v, g, beta, state


def _by_value_head(x, nh):
    """The test's own statement of the grouping, not the kernels' helper."""
    rep = nh // x.shape[1]
    return jnp.stack([x[:, h // rep] for h in range(nh)], axis=1)


@pytest.mark.parametrize("impl", ["reference", "jnp", "pallas", "update"])
@pytest.mark.parametrize("widths", sorted(GROUPED))
def test_grouped_key_heads_match_the_recurrence(impl, widths):
    """All four implementations at 'Hk' key heads under 'H' value heads
    against the float32 recurrence run on q and k repeated by hand; and a
    grouping by ``h % Hk`` must NOT match (the fault the layout invites)."""
    nh, nk, dk, dv = GROUPED[widths]
    slots = 3
    if impl == "update":
        T = slots
        start, length = np.arange(slots), np.array([1, 0, 1])
        fresh = np.array([0, 0, 1], bool)
    else:
        T = 70
        start, length = np.array([1, 0, 30]), np.array([29, 0, 38])
        fresh = np.array([0, 0, 1], bool)
    q, k, v, g, beta, state = _grouped_inputs(21, T, GROUPED[widths], slots)
    seg = np.full(T, slots, np.int32)
    first = np.zeros(T, bool)
    for r in range(slots):
        seg[start[r]:start[r] + length[r]] = r
        if fresh[r] and length[r]:
            first[start[r]] = True
    if impl == "reference":
        o, st = gdr.gdn_reference(q, k, v, g, beta, state, layer=1, seg=seg,
                                  first=first)
    elif impl == "update":
        o, st = gdr.gdn_recurrent_update(q, k, v, g, beta, state, layer=1,
                                         live=length > 0, fresh=fresh)
    else:
        fn = gdr.gdn_chunk_scan if impl == "pallas" \
            else gdr.gdn_chunk_scan_jnp
        o, st = fn(q, k, v, g, beta, state, layer=1, start=start,
                   length=length, fresh=fresh)
    want_o, want_st = _oracle(_by_value_head(q, nh), _by_value_head(k, nh),
                              v, g, beta, state, 1, start, length, fresh)
    rows = seg < slots
    _close(np.asarray(o)[rows], want_o[rows])
    _close(np.asarray(st)[1, [0, 2]], want_st[1, [0, 2]])
    assert np.array_equal(np.asarray(st)[1, 1], np.asarray(state)[1, 1])
    assert np.array_equal(np.asarray(st)[0], np.asarray(state)[0])
    # value head h on key head h % Hk is another model
    wrong = jnp.stack([q[:, h % nk] for h in range(nh)], axis=1)
    bad_o, _ = _oracle(wrong, _by_value_head(k, nh), v, g, beta, state, 1,
                       start, length, fresh)
    assert float(np.abs(bad_o[rows] - want_o[rows]).max()) > 1e-2
