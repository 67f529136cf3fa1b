"""The selective scan's implementations (``kernels.selective_scan``) against
the token-by-token recurrence, the two Pallas kernels in interpret mode; and
the ragged kernel's WINDOW (``kernels.pallas_ragged_attention``, ISSUE 37)
against ``ragged_attention_reference`` under the same mask, with no window
equal to the unwindowed kernel to the bit: work list, grid counts, output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import pallas_ragged_attention as ra
from paddle_tpu.kernels import selective_scan as ss

C, N, R = 256, 16, 5


def _inputs(seed, T):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jax.random.uniform(ks[0], (T, C), minval=0.001, maxval=0.1)
    u = dt * jax.random.normal(ks[1], (T, C))
    b = jax.random.normal(ks[2], (T, N))
    c = jax.random.normal(ks[3], (T, N))
    a = -jnp.exp(jax.random.uniform(ks[4], (N, C), minval=0.0, maxval=2.8))
    state = jax.random.normal(ks[5], (2, R, N, C))
    return dt, u, b, c, a, state


def _oracle(dt, u, b, c, a, state, layer, start, length, fresh):
    """Every span through ``ssm_recurrence``, by hand."""
    y = np.zeros(dt.shape, np.float32)
    st = np.array(state)
    for r in range(len(start)):
        if length[r] == 0:
            continue
        sl = slice(start[r], start[r] + length[r])
        s0 = None if fresh[r] else state[layer, r]
        y_r, s_r = ss.ssm_recurrence(dt[sl], u[sl], b[sl], c[sl], a, s0)
        y[sl] = np.asarray(y_r)
        st[layer, r] = np.asarray(s_r)
    return y, st


def _close(got, want, tol=1e-5):
    want = np.asarray(want)
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(np.asarray(got) - want).max()) <= tol * scale


SPANS = {
    # two spans that share a block of the packed buffer, one fresh, and a
    # slot with nothing
    "shared_block": ([3, 0, 40, 0, 0], [37, 0, 100, 0, 0], [0, 0, 1, 0, 0]),
    # lengths that are no multiple of the chunk or of a load of 8 tokens
    "odd": ([0, 70, 0, 75, 0], [70, 5, 0, 129, 0], [1, 0, 0, 0, 0]),
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
    start, length, fresh = (np.asarray(x) for x in SPANS[case])
    T = 210
    args = _inputs(1, T)
    want_y, want_s = _oracle(*args, 1, start, length, fresh)
    got_y, got_s = ss.ssm_chunk_scan(*args, layer=1, start=start,
                                     length=length, fresh=fresh.astype(bool))
    live = _rows(start, length, T)
    _close(np.where(live[:, None], got_y, 0), want_y)
    _close(got_s, want_s)
    # the oracle over the packed buffer says the same
    seg = np.full(T, R, np.int32)
    first = np.zeros(T, bool)
    for r in range(R):
        seg[start[r]:start[r] + length[r]] = r
        first[start[r]] |= bool(fresh[r] and length[r])
    ref_y, ref_s = ss.ssm_reference(*args, layer=1, seg=seg, first=first)
    _close(np.where(live[:, None], ref_y, 0), want_y)
    _close(ref_s, want_s)


def test_a_decode_only_buffer_walks_a_short_list():
    """``min_span=2``: a buffer of R rows has room for R // 2 spans."""
    T = 8
    dt, u, b, c, a, state = _inputs(2, T)
    start, length = np.array([0, 0, 2, 0, 0]), np.array([0, 0, 6, 0, 0])
    fresh = np.zeros(R, bool)
    want_y, want_s = _oracle(dt, u, b, c, a, state, 0, start, length, fresh)
    got_y, got_s = ss.ssm_chunk_scan(dt, u, b, c, a, state, layer=0,
                                     start=start, length=length, fresh=fresh,
                                     min_span=2)
    _close(got_y[2:], want_y[2:])
    _close(got_s, want_s)


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0] * 5, [1] * 5],
                         ids=["some", "none", "all"])
def test_recurrent_update_equals_recurrence(live):
    live = np.asarray(live, bool)
    fresh = np.array([0, 0, 1, 0, 1], bool)
    dt, u, b, c, a, state = _inputs(3, R)
    got_y, got_s = ss.ssm_recurrent_update(dt, u, b, c, a, state, layer=1,
                                           live=live, fresh=fresh)
    want_s = np.array(state)
    for r in np.flatnonzero(live):
        y, s = ss.ssm_recurrence(dt[r:r + 1], u[r:r + 1], b[r:r + 1],
                                 c[r:r + 1], a,
                                 None if fresh[r] else state[1, r])
        _close(got_y[r], y[0])
        want_s[1, r] = np.asarray(s)
    _close(got_s, want_s)


# ------------------------------------------------- the ragged kernel's window
BS, NB, HKV, D, G = 8, 40, 2, 16, 2
MB = 10
QSTART = np.array([0, 20, 21, 0], np.int32)
QLEN = np.array([20, 1, 30, 0], np.int32)
KVLEN = np.array([45, 70, 30, 0], np.int32)
T = 56


def _pools(seed=0):
    rng = np.random.default_rng(seed)
    pool_k = jnp.asarray(rng.normal(size=(2, NB, BS, HKV * D)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(2, NB, BS, HKV * D)), jnp.float32)
    tables = rng.permutation(NB)[:4 * MB].reshape(4, MB).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(T, HKV * G, D)), jnp.float32)
    return q, pool_k, pool_v, tables


def _kernel(window, pages, **kw):
    q, pool_k, pool_v, tables = _pools()
    return ra.ragged_paged_attention_pallas(
        q, pool_k, pool_v, tables, QSTART, QLEN, KVLEN, layer=1,
        window=window, block_q=16 * HKV * G, pages=pages, **kw)


@pytest.mark.parametrize("window,pages", [(5, 1), (16, 3), (33, 2)])
def test_windowed_kernel_equals_reference(window, pages):
    """A window inside a block, across blocks, across groups; a chunk, a
    decode row and a fresh prompt in one buffer."""
    q, pool_k, pool_v, tables = _pools()
    want = ra.ragged_attention_reference(
        q, pool_k, pool_v, tables, QSTART, QLEN, KVLEN, layer=1,
        window=window)
    full = ra.ragged_attention_reference(
        q, pool_k, pool_v, tables, QSTART, QLEN, KVLEN, layer=1)
    assert float(jnp.abs(want - full).max()) > 1e-2     # the window binds
    _close(_kernel(window, pages), want, tol=1e-5)


def test_no_window_is_the_unwindowed_kernel_to_the_bit():
    geometry = dict(nq=4, tokens_per_block=16, block_size=BS,
                    table_entries=MB)
    spans = tuple(jnp.asarray(x) for x in (QSTART, QLEN, KVLEN))
    plain = ra._work_list(*spans, **geometry)
    assert len(plain) == 4          # the list the kernel had before a window
    wide = ra._work_list(*spans, **geometry, window=1 << 20, pages=3)
    for a, b in zip(plain, wide):
        assert (np.asarray(a) == np.asarray(b)).all()
    assert not np.asarray(wide[4]).any()
    counts = dict(heads=HKV * G, block_size=BS, table_entries=MB,
                  packed_tokens=T, block_q=16 * HKV * G, pages=3)
    plain_counts = ra.ragged_grid_counts(QSTART, QLEN, KVLEN, **counts)
    assert plain_counts == ra.ragged_grid_counts(QSTART, QLEN, KVLEN,
                                                 **counts, window=1 << 20)
    # blocks up to each pair's diagonal, query blocks of 16 tokens: the
    # chunk's two, the decode row, the fresh prompt's three
    assert plain_counts["live_steps"] == (6 + 6) + 9 + (2 + 4 + 4)
    assert (np.asarray(_kernel(None, 3))
            == np.asarray(_kernel(1 << 20, 3))).all()


def test_grid_counts_follow_the_window():
    counts = dict(heads=HKV * G, block_size=BS, table_entries=MB,
                  packed_tokens=T, block_q=16 * HKV * G)
    got = ra.ragged_grid_counts(QSTART, QLEN, KVLEN, **counts, pages=1,
                                window=5)
    # first blocks: the chunk at positions 25..44 (its first query block
    # sees from 25 - 4 = 21: block 2; its second from 41 - 4 = 37: block 4),
    # the decode row at 69 (from 65: block 8), the fresh prompt (from 0, from
    # 11 - 4 = 7: block 0, from 27 - 4 = 23: block 2)
    assert got["live_steps"] == (6 - 2) + (6 - 4) + (9 - 8) + 2 + 4 + (4 - 2)
    assert got["kv_tokens"] == (20 + 4) + (1 + 4) + 30
    aligned = ra.ragged_grid_counts(QSTART, QLEN, KVLEN, **counts, pages=4,
                                    window=5)
    # a walk starts at a whole group of 4 blocks
    assert aligned["live_steps"] == 6 + (6 - 4) + (9 - 8) + 2 + 4 + 4
