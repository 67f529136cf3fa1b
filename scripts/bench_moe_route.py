#!/usr/bin/env python3
"""``moe_route`` alone on the chip: the XLA glue round the routed FFN's
grouped matmuls (``kernels/moe_ffn.py``, scope ``moe`` > ``moe_route``), piece
by piece, at the six routed cells' shapes and at each cell's two packed sizes
(its decode rows, and those plus a 512-token chunk) or at ``--rows`` (the
whole-prompt programs' 1,024 ... 8,192).

A piece's time is the difference of two PREFIXES of the layer call, each
timed inside ONE program (a loop over ``--layers`` layer calls that carries a
scalar every call's input depends on, so nothing is hoisted or shared):

- ``full`` (the buffer of ``rows x top_k`` pair slots; what every layer call
  ran before PR 57, and what a layer whose experts are all held still runs):
  ``router`` (the float32 product), ``score`` (softmax or sigmoid, the group
  limit), ``top_k`` (and the weights' rule), ``counts`` (the compare-and-sum
  and the stats), ``sort`` (the stable argsort of the pairs by expert, the
  pad), ``gather`` (``xs``), ``unsort`` (the second argsort, which inverts the
  first), ``back`` (``y`` gathered to (row, pick) order, widened, selected,
  weighted, summed; ``y`` is ``xs`` here: no grouped matmul runs);
- ``held`` (PR 57: one pass on the buffer of ``_capacity`` slots, only where
  that is smaller): ``sort``, ``gather``, and the two ways back the kernel
  file chooses between by ``PRODUCT_SLOTS``: ``back_product`` (the float32
  product of a ``[rows, capacity]`` matrix of weights with ``y``, three
  passes) and ``back_gather`` (the second argsort and the gather by pair);
- ``moe_ffn`` itself, whole, grouped matmuls and all, from ``--repo``: the
  parent's checkout gives the "before" of the same call; with ``--ways``,
  under a held range, once with each way back forced (``PRODUCT_SLOTS`` set
  over and under the buffer), which is where the two cross.

The forms PR 57 tried and dropped (one sort of keys ``expert x pairs + pair``;
a pair's slot from a running sum of the one-hot; the product at six passes)
are in PERF.md's table, section 6, and no longer here.

Prints one JSON line a (cell, rows): ms a layer call of every prefix and, by
difference, us a piece. What PERF.md (PR 57) says of the pair buffer is this
script's output.

    chiprun -- python3 scripts/bench_moe_route.py [--cells qwen3next,mimo]
        [--rows 1024,8192] [--layers N] [--iters N] [--repo DIR] [--ways]

(``--rehearse`` off the chip: ``scripts/kernel_bench.py``.)
"""
import sys

import kernel_bench

#: (name, decode rows, top_k, router width, held, hidden, expert width,
#: sigmoid router with a bias, n_group, topk_group, two-matrix expert)
CELLS = (
    ("olmoe", 24, 8, 64, 64, 2048, 1024, False, 1, 1, False),
    ("dsv2", 32, 6, 160, 20, 5120, 1536, False, 8, 3, False),
    ("glm52", 16, 8, 256, 16, 6144, 2048, True, 1, 1, False),
    ("nemotron3nano", 32, 6, 128, 16, 2688, 1856, True, 1, 1, True),
    ("qwen3next", 128, 10, 512, 64, 2048, 512, False, 1, 1, False),
    ("mimo", 32, 8, 256, 16, 4096, 2048, True, 1, 1, False),
)
CHUNK = 512
FULL = ("router", "score", "top_k", "counts", "sort", "gather", "unsort",
        "back")
HELD = ("counts", "sort", "gather", "back_product", "back_gather")


def main():
    ap = kernel_bench.arguments(__doc__, iters=20)
    ap.add_argument("--cells", default=",".join(c[0] for c in CELLS))
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--rows", default="",
                    help="row counts in place of each cell's two packed sizes")
    ap.add_argument("--whole-only", action="store_true",
                    help="time moe_ffn alone (a parent's checkout)")
    ap.add_argument("--ways", action="store_true",
                    help="time moe_ffn with each way back forced")
    a = ap.parse_args()
    platform, tiny = kernel_bench.start(a)
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels import moe_ffn as mf

    f32, tile = jnp.float32, mf.PAIR_TILE
    barrier = jax.lax.optimization_barrier      # a buffer a kernel reads
    hi = jax.lax.Precision.HIGHEST

    def one(cell, rows):
        name, _, top_k, n_exp, n_held, hid, inter, bias, n_group, topk_group, \
            two = cell
        if tiny:
            hid, inter = 128, 64
        dt = jnp.float32 if tiny else jnp.bfloat16
        ks = jax.random.split(jax.random.PRNGKey(a.seed % (2 ** 31)), 6)

        def normal(key, shape, scale):
            return (jax.random.normal(key, shape, f32) * scale).astype(dt)

        # arguments of every timed program, never constants inside one
        arrs = dict(
            h=normal(ks[0], (rows, hid), 1.0),
            router=normal(ks[1], (hid, n_exp), hid ** -0.5),
            w_up=normal(ks[3], (n_held, inter, hid) if two
                        else (n_held, hid, inter), 0.02),
            w_down=normal(ks[5], (n_held, inter, hid), 0.02))
        if bias:
            arrs["bias"] = jax.random.normal(ks[2], (n_exp,), f32) * 0.01
        if not two:
            arrs["w_gate"] = normal(ks[4], (n_held, hid, inter), 0.02)
        pairs = rows * top_k
        slots = -(-pairs // tile) * tile
        # (a parent's kernel file has no ``_capacity``: its formula then)
        cap = mf._capacity(pairs, n_held, n_exp) \
            if hasattr(mf, "_capacity") else min(
                slots, -(-2 * pairs * n_held // (n_exp * tile)) * tile)
        cap = slots if cap is None else cap

        def routing(arrs):
            return dict(top_k=top_k, live=jnp.ones(rows, bool),
                        renormalize=True, n_group=n_group,
                        topk_group=topk_group, router_bias=arrs.get("bias"))

        def route(arrs, h2):
            kw = routing(arrs)
            return mf._route(h2, arrs["router"], kw.pop("top_k"),
                             kw.pop("live"), kw.pop("renormalize"), n_held,
                             **kw)[:4]

        def full(arrs, h2, upto):
            """The prefix of the layer call on a slot a pick that ends with
            ``upto``."""
            logits = jnp.dot(h2.astype(f32), arrs["router"].astype(f32),
                             precision=hi)
            if upto == "router":
                return jnp.sum(logits)
            if upto in ("score", "top_k"):
                p = jax.nn.sigmoid(logits) + arrs["bias"] if bias \
                    else jax.nn.softmax(logits, -1)
                p = mf.group_limited(p, n_group, topk_group)
                if upto == "score":
                    return jnp.sum(p)
                wt, idx = jax.lax.top_k(p, top_k)
                return jnp.sum(wt) + jnp.sum(idx)
            w, _, idx, counts = route(arrs, h2)
            if upto == "counts":
                return jnp.sum(w) + jnp.sum(idx) + jnp.sum(counts)
            order = jnp.argsort(idx.reshape(-1), stable=True)
            order = jnp.pad(order, (0, slots - pairs))
            if upto == "sort":
                return jnp.sum(w) + jnp.sum(order) + jnp.sum(counts)
            xs = barrier(jnp.take(h2, order // top_k, axis=0))
            if upto == "gather":
                return jnp.sum(w) + jnp.sum(counts) + jnp.sum(
                    xs.astype(f32))
            slot_of = jnp.argsort(order[:pairs])
            if upto == "unsort":
                return jnp.sum(w) + jnp.sum(counts) + jnp.sum(
                    xs.astype(f32)) + jnp.sum(slot_of)
            y = jnp.take(xs, slot_of, axis=0).reshape(rows, top_k, hid)
            y = jnp.where((idx < n_held)[:, :, None], y.astype(f32), 0.0)
            return jnp.sum(jnp.sum(y * w[:, :, None], axis=1).astype(dt)
                           .astype(f32)) + jnp.sum(counts)

        def held(arrs, h2, upto):
            """The prefix of ONE pass of the layer call on ``cap`` slots
            (``moe_ffn``'s ``held_pairs`` without its loop)."""
            w, _, idx, counts = route(arrs, h2)
            if upto == "counts":
                return jnp.sum(w) + jnp.sum(idx) + jnp.sum(counts)
            order = jnp.argsort(idx.reshape(-1), stable=True)
            window = jnp.pad(order, (0, cap))[:cap]
            if upto == "sort":
                return jnp.sum(w) + jnp.sum(window) + jnp.sum(counts)
            row_of = window // top_k
            y = barrier(jnp.take(h2, row_of, axis=0))
            if upto == "gather":
                return jnp.sum(w) + jnp.sum(counts) + jnp.sum(y.astype(f32))
            if upto == "back_product":
                used = jnp.arange(cap) < jnp.sum(counts)
                sound = used & jnp.all(jnp.isfinite(y), axis=1)
                ws = jnp.where(used, jnp.where(
                    sound, jnp.take(w.reshape(-1), window), jnp.nan), 0.0)
                by_row = jnp.where(
                    row_of[None, :] == jnp.arange(rows)[:, None],
                    ws[None, :], 0.0)
                out = jnp.dot(
                    by_row, jnp.where(sound[:, None], y.astype(f32), 0.0),
                    precision=(hi, jax.lax.Precision.DEFAULT
                               if dt == jnp.bfloat16 else hi))
            else:
                at = jnp.argsort(order).reshape(rows, top_k).T
                here = (idx.T < n_held) & (at < cap)
                y = jnp.take(y, jnp.clip(at, 0, cap - 1).reshape(-1),
                             axis=0).reshape(top_k, rows, hid)
                y = jnp.where(here[:, :, None], y.astype(f32), 0.0)
                out = jnp.sum(y * w.T[:, :, None], axis=0)
            return jnp.sum(out.astype(dt).astype(f32)) + jnp.sum(counts)

        def whole(arrs, h2, _):
            out, stats = mf.moe_ffn(h2, arrs["router"], arrs.get("w_gate"),
                                    arrs["w_up"], arrs["w_down"],
                                    **routing(arrs))
            return jnp.sum(out.astype(f32)) + jnp.sum(stats)

        def ms_a_call(fn, upto):
            def program(acc, arrs):
                return jax.lax.fori_loop(
                    0, a.layers,
                    lambda _, acc: 1e-30 * fn(
                        arrs, arrs["h"] + (0.0 * acc).astype(dt), upto), acc)
            program = jax.jit(program)
            _, ms, _ = kernel_bench.timed(lambda acc: program(acc, arrs),
                                          f32(0), a.iters)
            return ms / a.layers

        out = {"moe_ffn_ms": ms_a_call(whole, None)}
        if a.ways and cap < slots and hasattr(mf, "PRODUCT_SLOTS"):
            shipped = mf.PRODUCT_SLOTS
            for way, bar in (("product", cap), ("gather", 0)):
                mf.PRODUCT_SLOTS = bar
                out["moe_ffn_ms_" + way] = ms_a_call(whole, None)
            mf.PRODUCT_SLOTS = shipped
            out["product_slots"] = shipped
        if not a.whole_only:
            t = {k: ms_a_call(full, k) for k in FULL}
            out.update({"full_ms_" + k: v for k, v in t.items()})
            out.update({"full_us_" + k: 1e3 * (t[k] - t[p])
                        for p, k in zip(FULL, FULL[1:])})
            if cap < slots:
                t = {k: ms_a_call(held, k) for k in HELD}
                out.update({"held_ms_" + k: v for k, v in t.items()})
                out.update({
                    "held_us_sort": 1e3 * (t["sort"] - t["counts"]),
                    "held_us_gather": 1e3 * (t["gather"] - t["sort"]),
                    "held_us_back_product": 1e3 * (t["back_product"]
                                                   - t["gather"]),
                    "held_us_back_gather": 1e3 * (t["back_gather"]
                                                  - t["gather"])})
        kernel_bench.line(name, platform, rows=rows, pairs=pairs,
                          slots=slots, capacity=cap, held=n_held,
                          router_width=n_exp, **out)

    want = a.cells.split(",")
    for cell in CELLS:
        if cell[0] in want:
            packed = (40, 72) if tiny else (cell[1], cell[1] + CHUNK)
            for rows in [int(r) for r in a.rows.split(",") if r] or packed:
                one(cell, rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
