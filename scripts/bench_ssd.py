#!/usr/bin/env python3
"""The Mamba-2 kernels alone on the chip, at the published widths
(``kernels/ssd.py``; 64 heads x 64 channels in 8 groups over a state of 128,
float32) and at the serving cell's sizes: 32 slots, 23 blocks, a store of
1.44 GiB.

Times a step's calls inside ONE program (a loop over the 23 blocks that
carries the store, donated, as the step's scan over its units does): the
decode-only step's ``ssd_recurrent_update`` of 32 rows, and a chunk step's
update of 31 rows and ``ssd_chunk_scan`` of one 512-token chunk in a packed
buffer of 544. Prints one JSON line a case: ms a call, us a row beside the
us of a row's bytes (the state read and written once, at 819 GB/s), and the
update's ``y`` against the token-by-token recurrence on one block. What
PERF.md (PR 48) says of the store's layout is this script's output, from the
parent's checkout and from the change's.

    chiprun -- python3 scripts/bench_ssd.py [--seed N] [--iters N] [--repo DIR]

(``--rehearse`` off the chip: ``scripts/kernel_bench.py``.)
"""
import sys

import kernel_bench
from kernel_bench import HBM_GBPS


def main():
    a = kernel_bench.arguments(__doc__, iters=20).parse_args()
    platform, tiny = kernel_bench.start(a)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import ssd

    H, P, G, N = (4, 8, 2, 16) if tiny else (64, 64, 8, 128)
    R, LL, chunk = (6, 2, 100) if tiny else (32, 23, 512)
    T = R + chunk
    f32 = jnp.float32
    # (a parent from before PR 48 keeps a head's state [P, N] and says so
    # nowhere)
    shape = ssd.state_shape(H, P, G, N) if hasattr(ssd, "state_shape") \
        else (H, P, N)
    ks = jax.random.split(jax.random.PRNGKey(a.seed % (2 ** 31)), 6)
    x = jax.random.normal(ks[0], (T, H, P), f32)
    dt = jax.random.uniform(ks[1], (T, H), f32, 1e-3, 0.1)
    b = jax.random.normal(ks[2], (T, G, N), f32)
    c = jax.random.normal(ks[3], (T, G, N), f32)
    av = -jnp.exp(jax.random.uniform(ks[4], (H,), f32, 0.0, 2.8))
    store = jax.random.normal(ks[5], (LL, R) + shape, f32)
    row_bytes = 2 * 4 * H * P * N
    none = np.zeros(R, bool)

    def update(store, live):
        def block(layer, carry):
            st, acc = carry
            y, st = ssd.ssd_recurrent_update(
                x[:R] + 0.0 * acc, dt[:R], av, b[:R], c[:R], st, layer=layer,
                live=live, fresh=none)
            return st, y[R - 1, 0, 0]
        return jax.lax.fori_loop(0, LL, block, (store, f32(0)))

    start, length = np.zeros(R, np.int32), np.zeros(R, np.int32)
    start[0], length[0] = R - 1, chunk

    def scan(store):
        def block(layer, carry):
            st, acc = carry
            y, st = ssd.ssd_chunk_scan(
                x + 0.0 * acc, dt, av, b, c, st, layer=layer, start=start,
                length=length, fresh=none)
            return st, y[R - 1, 0, 0]
        return jax.lax.fori_loop(0, LL, block, (store, f32(0)))

    def timed(fn, store, *args):
        fn = jax.jit(fn, donate_argnums=(0,) if not tiny else ())
        store, ms, first = kernel_bench.timed(
            lambda st: fn(st, *args)[0], store, a.iters)
        return store, ms / LL, first

    def line(case, **out):
        kernel_bench.line(case, platform, widths=[H, P, G, N], slots=R,
                          blocks=LL, **out)

    # the update's y on block 0 against the recurrence, before any timing
    # moves the store
    if hasattr(ssd, "state_from_store"):
        heads = ssd.state_from_store(store[0], H)
    else:
        heads = store[0]
    want = jax.vmap(lambda *t: ssd.ssd_recurrence(
        *(v[None] for v in t[:2]), av, *(v[None] for v in t[2:4]),
        t[4])[0][0])(x[:R], dt[:R], b[:R], c[:R], heads)
    got, _ = ssd.ssd_recurrent_update(
        x[:R], dt[:R], av, b[:R], c[:R], store, layer=0,
        live=np.ones(R, bool), fresh=none)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    del heads, want, got

    for case, live in (("decode_update", np.ones(R, bool)),
                       ("chunk_update", np.arange(R) > 0)):
        store, ms, first = timed(update, store, live)
        rows = int(live.sum())
        line(case, rows=rows, update_call_ms=ms, us_a_row=1e3 * ms / rows,
             us_of_a_rows_bytes=row_bytes / HBM_GBPS / 1e3,
             share_of_bytes_pct=100 * row_bytes / HBM_GBPS / 1e3
             / (1e3 * ms / rows),
             first_call_s=first, y_rel_err_block0=err)
    store, ms, first = timed(scan, store)
    line("chunk_scan", tokens=chunk, packed=T, scan_call_ms=ms,
         us_a_token=1e3 * ms / chunk, first_call_s=first)
    return 0


if __name__ == "__main__":
    sys.exit(main())
