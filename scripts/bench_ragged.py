#!/usr/bin/env python3
"""The ragged attention kernel alone on the chip, at a serving cell's
geometry and a given step (``kernels/pallas_ragged_attention.py``; the
defaults are ``serve-jamba2-longdoc-prefill``'s: 20 query heads of 128 on one
KV head, pool blocks of 32, 16 slots x 1,024 table entries, a packed buffer
of 16 + 512 tokens, bfloat16).

A step is ``--decode-rows`` spans of one token over ``--decode-kv`` cached
keys each, then one span of ``--chunk`` tokens behind a prefix; ``--prefix``
takes several, one case each, and each case is timed whole and in its two
halves (the decode rows alone, the chunk alone), so that what a change does
to a decode row's walk and to a chunk's can be told apart. The calls of a
case run inside ONE program (a loop whose carry feeds the next call's query,
as a step's layers follow each other). Prints one JSON line a case: ms a
call, the online-softmax updates and one-token rows the call makes and the
plane rows its general walk's updates work on (``ragged_grid_counts`` at the
kernel's own ``grid_params``; ``span_row_groups`` where the checkout counts
it), us an update, ns a plane row and group of the general walk (the chunk
case is that walk alone), and the output's largest error against a
span-by-span float32 softmax. ``--window`` times a window layer's call
(Phi-4-mini-flash's: ``--heads 20 --kv-heads 10 --window 512``). What PERF.md
(PR 51, PR 53) says of the kernel alone is this script's output, from the
parent's checkout and from the change's; it runs no code a cell runs but the
kernel.

    chiprun -- python3 scripts/bench_ragged.py [--repo DIR] [--prefix 0,8192]

(``--rehearse`` off the chip: ``scripts/kernel_bench.py``.)
"""
import sys

import kernel_bench


def main():
    ap = kernel_bench.arguments(__doc__, iters=5)
    ap.add_argument("--calls", type=int, default=20,
                    help="kernel calls inside one timed program")
    ap.add_argument("--heads", type=int, default=20)
    ap.add_argument("--kv-heads", type=int, default=1)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=32)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--table-entries", type=int, default=1024)
    ap.add_argument("--decode-rows", type=int, default=8)
    ap.add_argument("--decode-kv", type=int, default=16384)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--prefix", default="0,8192,24064",
                    help="cached keys before the chunk, one case each")
    ap.add_argument("--block-tokens", type=int, default=None,
                    help="the query block in tokens (default: the kernel's)")
    ap.add_argument("--window", type=int, default=None,
                    help="a query sees its last WINDOW keys only")
    a = ap.parse_args()
    platform, tiny = kernel_bench.start(a)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import pallas_ragged_attention as ragged

    H, Hkv, D, bs = a.heads, a.kv_heads, a.head_dim, a.block_size
    R, mb, rows, chunk = a.slots, a.table_entries, a.decode_rows, a.chunk
    decode_kv = a.decode_kv
    prefixes = [int(p) for p in a.prefix.split(",")]
    dtype = jnp.bfloat16
    if tiny:
        D, bs, R, mb, rows, chunk, decode_kv = 32, 16, 4, 16, 2, 40, 200
        prefixes, dtype = [0, 100], jnp.float32
    T, KD = R + chunk, Hkv * D
    block_q = None if a.block_tokens is None else a.block_tokens * H
    ks = jax.random.split(jax.random.PRNGKey(a.seed % (2 ** 31)), 3)
    q = jax.random.normal(ks[0], (T, H, D), dtype)
    pool_k = jax.random.normal(ks[1], (1, R * mb, bs, KD), dtype)
    pool_v = jax.random.normal(ks[2], (1, R * mb, bs, KD), dtype)
    tables = jnp.asarray(np.random.RandomState(a.seed % (2 ** 31))
                         .permutation(R * mb).reshape(R, mb), jnp.int32)
    tiling = ragged.grid_params(dtype, bs, KD, mb, H, T, block_q,
                                head_dim=D)

    def spans(decode, chunk_prefix):
        qs, ql, kl = (np.zeros(R, np.int32) for _ in range(3))
        if decode:
            qs[:rows], ql[:rows], kl[:rows] = np.arange(rows), 1, decode_kv
        if chunk_prefix is not None:
            qs[rows], ql[rows], kl[rows] = rows, chunk, chunk_prefix + chunk
        return qs, ql, kl

    @jax.jit
    def attend(q, pool_k, pool_v, tables, qs, ql, kl):
        return ragged.ragged_paged_attention_pallas(
            q, pool_k, pool_v, tables, qs, ql, kl, block_q=block_q, layer=0,
            window=a.window)

    @jax.jit
    def many(q, *args):
        def call(_, carry):
            out = attend(q + (0.0 * carry).astype(q.dtype), *args)
            return out[0, 0, 0].astype(jnp.float32)
        return jax.lax.fori_loop(0, a.calls, call, jnp.float32(0))

    def oracle_err(out, qs, ql, kl):
        # span by span, plain float32 softmax over the span's own cache
        worst = 0.0
        for r in range(R):
            n, kv = int(ql[r]), int(kl[r])
            if not n:
                continue
            blocks = tables[r, :-(-kv // bs)]
            k, v = (p[0, blocks].reshape(-1, Hkv, D)[:kv].astype(jnp.float32)
                    for p in (pool_k, pool_v))
            qr = q[int(qs[r]):int(qs[r]) + n].astype(jnp.float32).reshape(
                n, Hkv, H // Hkv, D)
            s = jnp.einsum("nkgd,skd->nkgs", qr, k,
                           precision="highest") / np.sqrt(D)
            pos, key = (kv - n + jnp.arange(n))[:, None], jnp.arange(kv)
            seen = key <= pos
            if a.window is not None:
                seen &= key > pos - a.window
            s = jnp.where(seen[:, None, None, :], s, -1e30)
            want = jnp.einsum("nkgs,skd->nkgd", jax.nn.softmax(s, -1), v,
                              precision="highest").reshape(n, H, D)
            got = out[int(qs[r]):int(qs[r]) + n].astype(jnp.float32)
            worst = max(worst, float(jnp.max(jnp.abs(got - want))
                                     / jnp.max(jnp.abs(want))))
        return worst

    for prefix in prefixes:
        for case, step in (("step", spans(True, prefix)),
                           ("decode_rows", spans(True, None)),
                           ("chunk", spans(False, prefix))):
            if case == "decode_rows" and prefix != prefixes[0]:
                continue            # the same rows whatever the chunk
            kw = dict(heads=H, block_size=bs, table_entries=mb,
                      packed_tokens=T, window=a.window, **tiling)
            try:
                counts = ragged.ragged_grid_counts(*step, kv_heads=Hkv, **kw)
            except TypeError:       # a checkout from before PR 53
                counts = ragged.ragged_grid_counts(*step, **kw)
            row_groups = counts.get("span_row_groups")
            args = (q, pool_k, pool_v, tables, *step)
            err = oracle_err(jax.block_until_ready(attend(*args)), *step)
            _, ms, first = kernel_bench.timed(
                lambda _: many(*args), None, a.iters)
            ms /= a.calls
            kernel_bench.line(
                case, platform, prefix=prefix, geometry=[H, Hkv, D],
                packed_tokens=T, decode_rows=rows if case != "chunk" else 0,
                decode_kv=decode_kv, block_tokens=tiling["block_q"] // H,
                pages=tiling["pages"], one_token=tiling["one_token"],
                window=a.window, update_steps=counts["update_steps"],
                one_token_rows=counts["one_token_rows"],
                span_row_groups=row_groups, call_ms=ms,
                us_an_update=1e3 * ms / max(1, counts["update_steps"]),
                ns_a_row_group=1e6 * ms / row_groups
                if row_groups and case == "chunk" else None,
                rel_err=err, first_call_s=first)
    return 0


if __name__ == "__main__":
    sys.exit(main())
