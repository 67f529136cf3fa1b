"""The Mamba-2 recurrence's implementations (``kernels.ssd``) against the
token-by-token recurrence, the two Pallas kernels in interpret mode: spans
that share a block of the packed buffer, a fresh span, a span longer than one
block, no span at all. The recurrence speaks ``[H, P, N]``; the store's layout
is ``ssd.state_to_store`` / ``state_from_store``'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import ssd

H, P, G, N, R = 4, 8, 2, 16, 5


def _inputs(seed, T, widths=(H, P, G, N), slots=R):
    H, P, G, N = widths
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (T, H, P))
    dt = jax.random.uniform(ks[1], (T, H), minval=0.001, maxval=0.1)
    b = jax.random.normal(ks[2], (T, G, N))
    c = jax.random.normal(ks[3], (T, G, N))
    a = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.8))
    state = jax.random.normal(ks[5], (2, slots) + ssd.state_shape(*widths))
    return x, dt, a, b, c, state


def _slot_state(store, layer, r):
    """A slot's state as the recurrence takes it, ``[H, P, N]``."""
    return ssd.state_from_store(store[layer, r], H)


def _oracle(x, dt, a, b, c, state, layer, start, length, fresh):
    """Every span through ``ssd_recurrence``, by hand."""
    y = np.zeros(x.shape, np.float32)
    st = np.array(state)
    for r in range(len(start)):
        if length[r] == 0:
            continue
        sl = slice(start[r], start[r] + length[r])
        s0 = None if fresh[r] else _slot_state(state, layer, r)
        y_r, s_r = ssd.ssd_recurrence(x[sl], dt[sl], a, b[sl], c[sl], s0)
        y[sl] = np.asarray(y_r)
        st[layer, r] = np.asarray(ssd.state_to_store(s_r, G))
    return y, st


def _close(got, want, tol=2e-5):
    want = np.asarray(want)
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * scale


SPANS = {
    # two spans that share a block of the packed buffer, one fresh, and a
    # slot with nothing
    "shared_block": ([3, 0, 40, 0, 0], [37, 0, 100, 0, 0], [0, 0, 1, 0, 0]),
    # lengths that are no multiple of the block; a span over three blocks
    "odd": ([0, 70, 0, 75, 0], [70, 5, 0, 135, 0], [1, 0, 0, 0, 0]),
    # no span at all: the store comes back as it was
    "none": ([0] * 5, [0] * 5, [0] * 5),
}


def _rows(start, length, T):
    live = np.zeros(T, bool)
    for s, n in zip(start, length):
        live[s:s + n] = True
    return live


@pytest.mark.parametrize("case", sorted(SPANS))
def test_chunk_scan_equals_recurrence(case):
    start, length, fresh = (np.asarray(v) for v in SPANS[case])
    T = 210
    args = _inputs(1, T)
    want_y, want_s = _oracle(*args, 1, start, length, fresh)
    got_y, got_s = ssd.ssd_chunk_scan(*args, layer=1, start=start,
                                      length=length,
                                      fresh=fresh.astype(bool))
    live = _rows(start, length, T)
    _close(np.where(live[:, None, None], got_y, 0), want_y)
    _close(got_s, want_s)
    # the oracle over the packed buffer says the same
    seg = np.full(T, R, np.int32)
    first = np.zeros(T, bool)
    for r, (s, n) in enumerate(zip(start, length)):
        seg[s:s + n] = r
        if n and fresh[r]:
            first[s] = True
    ref_y, ref_s = ssd.ssd_reference(*args, layer=1, seg=seg, first=first)
    _close(np.where(live[:, None, None], ref_y, 0), want_y)
    _close(ref_s, want_s)
    # the other layer of the store is nobody's
    np.testing.assert_array_equal(np.asarray(got_s[0]),
                                  np.asarray(args[-1][0]))


@pytest.mark.parametrize("live,fresh", [
    ([1, 0, 1, 1, 0], [0, 0, 1, 0, 0]),
    ([1, 1, 1, 1, 1], [0, 0, 0, 0, 1]),
    ([0, 0, 0, 0, 0], [0, 0, 0, 0, 0]),
])
def test_recurrent_update_equals_recurrence(live, fresh):
    live, fresh = np.asarray(live, bool), np.asarray(fresh, bool)
    x, dt, a, b, c, state = _inputs(2, R)
    got_y, got_s = ssd.ssd_recurrent_update(x, dt, a, b, c, state, layer=0,
                                            live=live, fresh=fresh)
    want_s = np.array(state)
    for r in range(R):
        if not live[r]:
            continue
        s0 = None if fresh[r] else _slot_state(state, 0, r)
        y_r, s_r = ssd.ssd_recurrence(x[r:r + 1], dt[r:r + 1], a,
                                      b[r:r + 1], c[r:r + 1], s0)
        _close(got_y[r], y_r[0])
        want_s[0, r] = np.asarray(ssd.state_to_store(s_r, G))
    _close(got_s, want_s)
    np.testing.assert_array_equal(np.asarray(got_s[1]), np.asarray(state[1]))


def test_steps_of_chunks_then_rows_equal_one_recurrence():
    """A sequence fed as a chunk, a second chunk from the stored state, then
    decode rows: the state a slot holds is the whole sequence's."""
    T = 150
    x, dt, a, b, c, state = _inputs(3, T)
    want_y, want_s = ssd.ssd_recurrence(x, dt, a, b, c)
    got = np.zeros(x.shape, np.float32)
    slot = 3

    def one(r):
        return np.asarray([r if i == slot else 0 for i in range(R)])

    for lo, hi in ((0, 70), (70, 147)):
        sl = slice(lo, hi)
        y, state = ssd.ssd_chunk_scan(
            x[sl], dt[sl], a, b[sl], c[sl], state, layer=1, start=one(0),
            length=one(hi - lo), fresh=one(lo == 0).astype(bool))
        got[sl] = np.asarray(y)
    for t in range(147, T):
        def rows(v):
            return jnp.zeros((R,) + v.shape[1:]).at[slot].set(v[t])
        y, state = ssd.ssd_recurrent_update(
            rows(x), rows(dt), a, rows(b), rows(c), state, layer=1,
            live=one(1).astype(bool), fresh=np.zeros(R, bool))
        got[t] = np.asarray(y[slot])
    _close(got, want_y)
    _close(_slot_state(state, 1, slot), want_s)


def test_recurrent_update_at_the_published_widths():
    """64 heads x 64 channels in 8 groups over a state of 128 (a group's
    ``[128, 512]`` is whole lane tiles): dead rows between live ones (the
    kernel walks the live rows first, so out of slot order), and a fresh row
    over a slot whose former tenant left ``NaN``."""
    H, P, G, N, R = 64, 64, 8, 128, 6
    x, dt, a, b, c, store = _inputs(4, R, (H, P, G, N), R)
    heads = ssd.state_from_store(store[0], H)
    live = np.asarray([0, 1, 0, 1, 1, 0], bool)
    fresh = np.asarray([0, 0, 0, 1, 0, 0], bool)
    got_y, got_s = ssd.ssd_recurrent_update(
        x, dt, a, b, c, store.at[0, 3].set(jnp.nan), layer=0, live=live,
        fresh=fresh)
    assert got_s.shape == store.shape
    np.testing.assert_array_equal(np.asarray(got_s[1]), np.asarray(store[1]))
    got_s = np.asarray(ssd.state_from_store(got_s[0], H))
    for r in range(R):
        if not live[r]:
            np.testing.assert_array_equal(got_s[r], np.asarray(heads[r]))
            continue
        y_r, s_r = ssd.ssd_recurrence(
            x[r:r + 1], dt[r:r + 1], a, b[r:r + 1], c[r:r + 1],
            None if fresh[r] else heads[r])
        _close(got_y[r], y_r[0], 1e-5)
        _close(got_s[r], s_r, 1e-5)


def test_the_store_layout_round_trip():
    """``[H, P, N]`` -> store -> ``[H, P, N]`` is the identity, element
    ``S[h, p, n]`` lies at ``[h // hg, n, (h % hg) P + p]``, and a state the
    chunk scan wrote is read by the update as the recurrence's."""
    s = jax.random.normal(jax.random.PRNGKey(5), (3, H, P, N))
    st = ssd.state_to_store(s, G)
    assert st.shape == (3,) + ssd.state_shape(H, P, G, N)
    np.testing.assert_array_equal(np.asarray(ssd.state_from_store(st, H)),
                                  np.asarray(s))
    hg = H // G
    for h, p, n in ((0, 0, 0), (1, 3, 5), (H - 1, P - 1, N - 1), (2, 7, 1)):
        assert float(st[1, h // hg, n, (h % hg) * P + p]) \
            == float(s[1, h, p, n])
    T = 70
    x, dt, a, b, c, state = _inputs(6, T + 1)
    slot = 2
    want_y, want_s = ssd.ssd_recurrence(x, dt, a, b, c)

    def one(r):
        return np.asarray([r if i == slot else 0 for i in range(R)])

    _, state = ssd.ssd_chunk_scan(
        x[:T], dt[:T], a, b[:T], c[:T], state, layer=0, start=one(0),
        length=one(T), fresh=one(1).astype(bool))

    def rows(v):
        return jnp.zeros((R,) + v.shape[1:]).at[slot].set(v[T])
    y, state = ssd.ssd_recurrent_update(
        rows(x), rows(dt), a, rows(b), rows(c), state, layer=0,
        live=one(1).astype(bool), fresh=np.zeros(R, bool))
    _close(y[slot], want_y[T])
    _close(_slot_state(state, 0, slot), want_s)
