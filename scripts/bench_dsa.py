#!/usr/bin/env python3
"""Sparse attention's three steps alone on the chip, at the published widths
(``kernels/dsa.py``; 64 heads over a latent of 512 + 64 in rows of 640 lanes,
an indexer of 32 heads of 128, 2,048 selected), for one layer call of the
two packed sizes the serving cell runs: 16 decode rows, and those 16 beside
one 512-token chunk, over caches of 6k-19k tokens scattered through a pool.

Times each step (index scores; the selection, the selection's layout, the
attention by walk-and-mask and by gather, the dense latent walk for scale;
the three kernel calls also inside a loop of one program, ``*_in_loop_ms``,
where the host's dispatch does not bound the reading),
checks the two ways of attending against each other, and prints one JSON line
a packed size. What PERF.md (PR 43) says of gather against walk-and-mask is
this script's output.

    chiprun -- python3 scripts/bench_dsa.py [--seed N] [--iters N]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--max-seq-len", type=int, default=20480)
    ap.add_argument("--topk", type=int, default=2048)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.kernels import dsa
    from paddle_tpu.kernels.pallas_mla_ragged_attention import \
        mla_ragged_attention_pallas

    platform = jax.devices()[0].platform
    tiny = platform != "tpu"        # a rehearsal: small and interpreted
    rng = np.random.RandomState(a.seed)
    R, bs = a.rows, 32
    s_max = 256 if tiny else a.max_seq_len
    topk = 16 if tiny else a.topk
    mb = s_max // bs
    nh, rank, rope, W, hi, d = (4, 32, 8, 128, 4, 16) if tiny \
        else (64, 512, 64, 640, 32, 128)
    chunk = 32 if tiny else 512
    bf16 = jnp.bfloat16
    nb = R * mb
    perm = rng.permutation(nb).astype(np.int32)
    tables = perm.reshape(R, mb)
    pool = jnp.asarray(rng.randn(1, nb, bs, W).astype(np.float32), bf16)
    pool = pool.at[..., rank + rope:].set(0)
    ipool = jnp.asarray(rng.randn(1, nb, bs, d).astype(np.float32), bf16)
    lo, hi_ctx = (s_max * 3) // 10, (s_max * 19) // 20

    def attention_by_gather(q_lat, q_pe, pool, tables, qstart, qlen, kvlen,
                            mask, *, scale, k):
        """The way the step programs do NOT take: each query's at most ``k``
        selected rows gathered through the tables and attended in the
        absorbed form, all heads on the same gathered rows."""
        rank, rope_w = q_lat.shape[-1], q_pe.shape[-1]
        rows, ok = dsa._gather_selected(pool, 0, tables, qstart, qlen, kvlen,
                                        mask, k)             # [T, k, W]
        q = jnp.concatenate([q_lat, q_pe], axis=-1)
        s = jnp.einsum("thw,tkw->thk", q, rows[..., :rank + rope_w],
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[:, None, :], s, dsa.NEG_INF)
        p = jnp.where(ok[:, None, :], jax.nn.softmax(s, axis=-1), 0.0)
        return jnp.einsum("thk,tkr->thr", p.astype(rows.dtype),
                          rows[..., :rank]).astype(q_lat.dtype)

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(a.iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return out, 1e3 * (time.perf_counter() - t0) / a.iters

    def timed_in_loop(fn, q, w, *rest, calls=4 if tiny else 100):
        """ms a call of ``calls`` calls inside ONE program, each fed by the
        one before it: the device's time. ``timed`` dispatches a program a
        call and reads no lower than the host's dispatch (0.20-0.22 ms a
        call on the chip's host, PERF.md, PR 44)."""
        def chain(q, w, *rest):
            def one(_, acc):
                res = fn(q, (w + 0.0 * acc).astype(w.dtype), *rest)
                return res[(0,) * res.ndim].astype(jnp.float32)
            return jax.lax.fori_loop(0, calls, one, jnp.float32(0))
        _, ms = timed(jax.jit(chain), q, w, *rest)
        return ms / calls

    for name, spans in (
            ("decode", [(1, int(k)) for k in rng.randint(lo, hi_ctx, R)]),
            ("chunk", [(1, int(k)) for k in rng.randint(lo, hi_ctx, R - 1)]
             + [(chunk, (s_max * 6) // 10)])):
        qlen = np.array([q for q, _ in spans], np.int32)
        kvlen = np.array([k for _, k in spans], np.int32)
        qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).astype(np.int32)
        T = -(-int(qlen.sum()) // 8) * 8
        span = tuple(jnp.asarray(x) for x in (tables, qstart, qlen, kvlen))
        q_lat = jnp.asarray(rng.randn(T, nh, rank).astype(np.float32)
                            * rank ** -0.5, bf16)
        q_pe = jnp.asarray(rng.randn(T, nh, rope).astype(np.float32)
                           * rank ** -0.5, bf16)
        q_i = jnp.asarray(rng.randn(T, hi, d).astype(np.float32), bf16)
        w_i = jnp.asarray(rng.randn(T, hi).astype(np.float32))
        out = {"case": name, "platform": platform, "packed_tokens": T,
               "rows": R, "mean_ctx": float(kvlen.mean()), "topk": topk}
        scores, out["index_scores_ms"] = timed(jax.jit(
            lambda q, w, p, *s: dsa.dsa_index_scores_pallas(q, w, p, *s)),
            q_i, w_i, ipool, *span)
        out["index_scores_in_loop_ms"] = timed_in_loop(
            dsa.dsa_index_scores_pallas, q_i, w_i, ipool, *span)
        mask, out["select_ms"] = timed(jax.jit(
            lambda s: dsa.dsa_select(s, topk)), scores)
        _, out["top_k_ms"] = timed(jax.jit(
            lambda s: jax.lax.top_k(s, topk)[1]), scores)
        bias, out["selection_bias_ms"] = timed(jax.jit(
            lambda m: dsa.selection_bias(m, nh, table_entries=mb,
                                         block_size=bs)), mask)
        def attend_masked(ql, qp, p, b, *s):
            return dsa.dsa_attention_pallas(ql, qp, p, *s, b, scale=0.1)

        def attend_dense(ql, qp, p, *s):
            return mla_ragged_attention_pallas(ql, qp, p, *s, scale=0.1)

        # (a chunk call is 10 ms: a fifth of the calls time it as well)
        calls = 4 if tiny else 100 if name == "decode" else 20
        walk, out["attend_walk_mask_ms"] = timed(
            jax.jit(attend_masked), q_lat, q_pe, pool, bias, *span)
        out["attend_walk_mask_in_loop_ms"] = timed_in_loop(
            attend_masked, q_lat, q_pe, pool, bias, *span, calls=calls)
        _, out["attend_dense_walk_ms"] = timed(
            jax.jit(attend_dense), q_lat, q_pe, pool, *span)
        out["attend_dense_walk_in_loop_ms"] = timed_in_loop(
            attend_dense, q_lat, q_pe, pool, *span, calls=calls)
        if name == "decode":
            got, out["attend_gather_ms"] = timed(jax.jit(
                lambda ql, qp, p, m, *s: attention_by_gather(
                    ql, qp, p, *s, m, scale=0.1, k=topk)), q_lat, q_pe,
                pool, mask, *span)
            live = int(qlen.sum())
            err = float(jnp.max(jnp.abs(
                got[:live].astype(jnp.float32)
                - walk[:live].astype(jnp.float32))))
            out["gather_minus_walk_max_abs"] = err
            out["walk_max_abs"] = float(jnp.max(jnp.abs(
                walk[:live].astype(jnp.float32))))
        n_sel = np.asarray(jnp.sum(mask, axis=-1))
        out["selected_rows"] = int(n_sel.sum())
        out["rows_a_dense_walk_reads"] = int(kvlen[qlen > 0].sum())
        print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                          for k, v in out.items()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
