#!/usr/bin/env python3
"""The Gated DeltaNet kernels alone on the chip, at the published widths
(``kernels/gated_delta_rule.py``; 30 heads of 96 keys x 192 values, float32)
and at the serving cell's sizes: 32 slots, 12 linear layers, a store of
0.79 GiB.

``scripts/bench_ssd.py``'s twin. Times a step's calls inside ONE program (a
loop over the 12 layers that carries the store, donated, as the step's scan
over its periods does): the decode-only step's ``gdn_recurrent_update`` of 32
rows, and a chunk step's update of 31 rows and ``gdn_chunk_scan`` of one
512-token chunk in a packed buffer of 544. Prints one JSON line a case: ms a
call (the XLA glue around the kernel included), us a row beside the us of a
row's bytes (the state read and written once, at 819 GB/s, as
``benchmark/flops_bytes_gdn.py`` counts them: no padding), and the update's
``o`` against the token-by-token recurrence on layer 0. What PERF.md (PR 49)
says of the store's layout is this script's output, from the parent's
checkout and from the change's.

    chiprun -- python3 scripts/bench_gdn.py [--seed N] [--iters N] [--repo DIR]

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

    from paddle_tpu.kernels import gated_delta_rule as gdr

    H, DK, DV = (4, 8, 48) if tiny else (30, 96, 192)
    R, LL, chunk = (6, 2, 100) if tiny else (32, 12, 512)
    T = R + chunk
    f32 = jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(a.seed % (2 ** 31)), 6)
    q = gdr.l2norm(jax.random.normal(ks[0], (T, H, DK), f32), DK ** -0.5)
    k = gdr.l2norm(jax.random.normal(ks[1], (T, H, DK), f32))
    v = jax.random.normal(ks[2], (T, H, DV), f32)
    g = -1.6 * jax.random.uniform(ks[3], (T, H), f32)
    beta = 2.0 * jax.random.uniform(ks[4], (T, H), f32)
    heads = jax.random.normal(ks[5], (LL, R, H, DK, DV), f32)
    # (a parent from before PR 49 keeps a head's state [dk, dv] and says so
    # nowhere)
    store = gdr.state_to_store(heads) if hasattr(gdr, "state_to_store") \
        else heads
    row_bytes = 2 * 4 * H * DK * DV
    none = np.zeros(R, bool)

    # the update's o on layer 0 against the recurrence, before any timing
    # moves the store
    want = jax.vmap(lambda *t: gdr.gdn_recurrence(
        *(x[None] for x in t[:5]), t[5])[0][0])(
            q[:R], k[:R], v[:R], g[:R], beta[:R], heads[0])
    got, _ = gdr.gdn_recurrent_update(
        q[:R], k[:R], v[:R], g[:R], beta[:R], store, layer=0,
        live=np.ones(R, bool), fresh=none)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    del heads, want, got

    def update(store, live):
        def layer(at, carry):
            st, acc = carry
            o, st = gdr.gdn_recurrent_update(
                q[:R], k[:R], v[:R] + 0.0 * acc, g[:R], beta[:R], st,
                layer=at, live=live, fresh=none)
            return st, o[R - 1, 0, 0]
        return jax.lax.fori_loop(0, LL, layer, (store, f32(0)))

    start, length = np.zeros(R, np.int32), np.zeros(R, np.int32)
    start[0], length[0] = R - 1, chunk

    def scan(store):
        def layer(at, carry):
            st, acc = carry
            o, st = gdr.gdn_chunk_scan(
                q, k, v + 0.0 * acc, g, beta, st, layer=at, start=start,
                length=length, fresh=none)
            return st, o[R - 1, 0, 0]
        return jax.lax.fori_loop(0, LL, layer, (store, f32(0)))

    def timed(fn, store, *args):
        fn = jax.jit(fn, donate_argnums=(0,) if not tiny else ())
        store, ms, first = kernel_bench.timed(
            lambda st: fn(st, *args)[0], store, a.iters)
        return store, ms / LL, first

    def line(case, **out):
        kernel_bench.line(case, platform, widths=[H, DK, DV], slots=R,
                          layers=LL, store_shape=list(store.shape), **out)

    for case, live in (("decode_update", np.ones(R, bool)),
                       ("chunk_update", np.arange(R) > 0)):
        store, ms, first = timed(update, store, live)
        rows = int(live.sum())
        line(case, rows=rows, update_call_ms=ms, us_a_row=1e3 * ms / rows,
             us_of_a_rows_bytes=row_bytes / HBM_GBPS / 1e3,
             share_of_bytes_pct=100 * row_bytes / HBM_GBPS / 1e3
             / (1e3 * ms / rows),
             first_call_s=first, o_rel_err_layer0=err)
    store, ms, first = timed(scan, store)
    line("chunk_scan", tokens=chunk, packed=T, scan_call_ms=ms,
         us_a_token=1e3 * ms / chunk, first_call_s=first)
    return 0


if __name__ == "__main__":
    sys.exit(main())
