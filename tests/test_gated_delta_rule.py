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


def _inputs(seed, T, g_lo=-1.6, g_hi=0.0, beta_lo=0.0, beta_hi=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdr.l2norm(jax.random.normal(ks[0], (T, H, DK)), DK ** -0.5)
    k = gdr.l2norm(jax.random.normal(ks[1], (T, H, DK)))
    v = jax.random.normal(ks[2], (T, H, DV))
    g = jax.random.uniform(ks[3], (T, H), minval=g_lo, maxval=g_hi)
    beta = jax.random.uniform(ks[4], (T, H), minval=beta_lo, maxval=beta_hi)
    state = jax.random.normal(ks[5], (2, R, H, DK, DV))
    return q, k, v, g, beta, state


def _oracle(q, k, v, g, beta, state, layer, start, length, fresh):
    """Every span through ``gdn_recurrence``, by hand."""
    o = np.zeros(v.shape, np.float32)
    st = np.array(state)
    for r in range(len(start)):
        if length[r] == 0:
            continue
        sl = slice(start[r], start[r] + length[r])
        s0 = None if fresh[r] else state[layer, r]
        o_r, s_r = gdr.gdn_recurrence(q[sl], k[sl], v[sl], g[sl], beta[sl],
                                      s0)
        o[sl] = np.asarray(o_r)
        st[layer, r] = np.asarray(s_r)
    return o, st


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
