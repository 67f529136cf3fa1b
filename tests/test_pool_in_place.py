"""The KV pool stays where it is (``BlockManager``'s stored layout and
sentinel rule): the unified step's packed forward appends a layer's rows at
``[layer, block, row]`` of the one stored buffer and the ragged kernel reads
its blocks from there. What keeps that true:

- addressing: a dropped write (dead packed row, sentinel table tail, a
  position past the table) changes nothing, least of all block 0 of the next
  layer, and layer ``l`` of the stack reads exactly what a call on that
  layer's slice alone reads;
- structure, on the step's jaxpr (no backend): the layer scan neither slices
  a pool layer out of its inputs nor stacks one into its outputs.

``tests/test_chip_bringup.py`` holds the same claim on the optimised HLO of
the TPU compiler.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import pallas_ragged_attention
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.block_manager import BlockManager

from serving_support import compiled_once

# one program for the stacked pool (``layer`` is a traced argument) and one
# for a layer cut out, not one a call
ragged_paged_attention_pallas = compiled_once(
    pallas_ragged_attention.ragged_paged_attention_pallas)

L, HID, NH, HD, FFN, VOCAB = 3, 32, 16, 8, 48, 64
R, MB, BS = 4, 3, 4             # slots, table entries a slot, rows a block
NB = 64                         # a pool layer is the largest array there is
S_TOT = MB * BS


def _params(nkv, seed=0):
    r = np.random.RandomState(seed)

    def w(*shape):
        return jnp.asarray(r.randn(*shape) * 0.2, jnp.bfloat16)
    return dict(embed=w(VOCAB, HID), wq=w(L, HID, NH * HD),
                wk=w(L, HID, nkv * HD), wv=w(L, HID, nkv * HD),
                wo=w(L, NH * HD, HID), w_gate=w(L, HID, FFN),
                w_up=w(L, HID, FFN), w_down=w(L, FFN, HID),
                input_ln=1 + w(L, HID), post_ln=1 + w(L, HID),
                final_norm=1 + w(HID), lm_head=w(HID, VOCAB))


def _step_inputs():
    """Four slots. 0: a decode row (5 rows of history, appends at 5). 1: a
    4-token chunk after 3 rows of history (crosses a block boundary). 2: a
    row whose token sits past the table (position 12 of 12): the write drops,
    it is never clamped into the row's last block. 3: idle, its table all
    sentinel. Tables never name block 0; tails are sentinel. The packed
    buffer ends in four dead rows (``seg == R``) at position 0."""
    tables = np.full((R, MB), NB, np.int32)
    tables[0, :2] = [5, 9]
    tables[1, :2] = [7, 3]
    tables[2] = [11, 2, 6]
    qstart = np.array([0, 1, 5, 0], np.int32)
    qlen = np.array([1, 4, 1, 0], np.int32)
    kvlen = np.array([6, 7, S_TOT + 1, 0], np.int32)
    seg = np.array([0, 1, 1, 1, 1, 2, R, R, R, R], np.int32)
    pos = np.array([5, 3, 4, 5, 6, S_TOT, 0, 0, 0, 0], np.int32)
    ids = np.arange(1, 11, dtype=np.int32)
    history = {0: 5, 1: 3, 2: S_TOT}        # rows valid before the step
    return tables, qstart, qlen, kvlen, seg, pos, ids, history


def _coords(tables, rows):
    """(block, row) of logical positions ``rows`` of one table row."""
    return [(int(tables[p // BS]), p % BS) for p in rows]


def _pool(nkv, kv_dtype, tables, history, seed=1):
    """A stored pool whose every stale row is poison — NaN, or on an int8
    pool the scale 1e30 (its kernel multiplies masked columns by their scale,
    so a NaN there poisons by design; 1e30 shows any row that leaks) — and
    whose history rows hold small values, different in every layer."""
    r = np.random.RandomState(seed)
    kd = nkv * HD
    shape = BlockManager.pool_shape(L, NB, BS, nkv, HD)
    live = [c for slot, n in history.items()
            for c in _coords(tables[slot], range(n))]
    b, w = (np.array(x) for x in zip(*live))
    sides = []
    for _ in range(2):
        if kv_dtype == "int8":
            data = np.full(shape, 77, np.int8)
            scale = np.full(shape[:-1] + (nkv,), 1e30, np.float32)
            data[:, b, w] = r.randint(-127, 128, (L, len(b), kd))
            scale[:, b, w] = r.rand(L, len(b), nkv) * 0.02 + 0.001
            sides.append((jnp.asarray(data), jnp.asarray(scale)))
        else:
            data = np.full(shape, np.nan, np.float32)
            data[:, b, w] = r.randn(L, len(b), kd)
            sides.append(jnp.asarray(data, jnp.bfloat16))
    return sides


def _bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


def _changed(before, after):
    """[L, NB, BS] bool: the rows whose bytes differ."""
    return (_bits(before) != _bits(after)).any(axis=-1)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("nkv", [8, 16], ids=["gqa8", "mha16"])
def test_appends_land_at_their_layer_and_nowhere_else(nkv, kv_dtype):
    tables, qstart, qlen, kvlen, seg, pos, ids, history = _step_inputs()
    pool_k, pool_v = _pool(nkv, kv_dtype, tables, history)
    params = _params(nkv)
    sin, cos = decode_mod._rope_tables(S_TOT, HD, 10000.0)
    x, pk, pv, stats = jax.jit(functools.partial(
        decode_mod._packed_span_forward, nh=NH, nkv=nkv, hd=HD, eps=1e-5,
        decode_attn="pallas"))(
            params, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(ids),
            jnp.asarray(seg), jnp.asarray(pos), jnp.asarray(qstart),
            jnp.asarray(qlen), jnp.asarray(kvlen), sin, cos)
    assert stats is None
    # stale rows are masked, never summed: no packed row met the poison
    assert (np.abs(np.asarray(x, np.float32)) < 1e4).all()

    # the rows this step may write: slot 0's position 5, slot 1's 3..6;
    # slot 2's token is past the table and the dead rows belong to no slot
    want = np.zeros((L, NB, BS), bool)
    for blk, row in _coords(tables[0], [5]) + _coords(tables[1], range(3, 7)):
        want[:, blk, row] = True
    for before, after in ((pool_k, pk), (pool_v, pv)):
        for i in range(2 if kv_dtype else 1):       # data, then scales
            b, a = (p[i] if kv_dtype else p for p in (before, after))
            np.testing.assert_array_equal(_changed(b, a), want)
            # a dropped row of layer l, flattened, would be block 0 of l + 1
            np.testing.assert_array_equal(_bits(b)[:, 0], _bits(a)[:, 0])
    written = np.asarray(pk[1] if kv_dtype else pk, np.float32)[want]
    assert (np.abs(written) < 1e4).all()


@functools.lru_cache(maxsize=None)
def _each_layer_both_ways(nkv, kv_dtype):
    """``[(the kernel on layer l of the stored pool, the kernel on layer l
    cut out and handed over as a pool of its own)]``, run once for the
    module: two programs a (heads, dtype), whichever case asks first."""
    tables, qstart, qlen, kvlen, _, _, _, history = _step_inputs()
    # every position a span attends over must hold a value
    history = {0: 6, 1: 7, 2: S_TOT}
    pool_k, pool_v = _pool(nkv, kv_dtype, tables, history)
    q = jnp.asarray(np.random.RandomState(3).randn(10, NH, HD), jnp.bfloat16)
    meta = tuple(jnp.asarray(a) for a in (tables, qstart, qlen, kvlen))
    kd, vd, ks, vs = decode_mod._kv_attn_args(pool_k, pool_v)
    outs = []
    for layer in range(L):
        got = ragged_paged_attention_pallas(
            q, kd, vd, *meta, k_scale=ks, v_scale=vs,
            layer=jnp.int32(layer))
        alone = ragged_paged_attention_pallas(
            q, kd[layer].reshape(NB, BS, nkv, HD),
            vd[layer].reshape(NB, BS, nkv, HD), *meta,
            k_scale=None if ks is None else ks[layer],
            v_scale=None if vs is None else vs[layer])
        outs.append((np.asarray(got), np.asarray(alone)))
    return outs


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("nkv", [8, 16], ids=["gqa8", "mha16"])
def test_layer_of_the_stack_reads_as_its_slice_alone(nkv, kv_dtype, layer):
    """Bitwise: the kernel on ``layer=l`` of the stored pool against the same
    kernel on layer ``l`` cut out and handed over as a pool of its own."""
    got, alone = _each_layer_both_ways(nkv, kv_dtype)[layer]
    np.testing.assert_array_equal(_bits(got), _bits(alone))
    assert (np.abs(got.astype(np.float32)) < 1e4).all()


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("nkv", [8, 16], ids=["gqa8", "mha16"])
def test_the_layers_of_the_stack_are_told_apart(nkv, kv_dtype):
    """Each layer holds other values, so a call that read a neighbour's
    blocks could not have passed for its own."""
    outs = [got.astype(np.float32)
            for got, _ in _each_layer_both_ways(nkv, kv_dtype)]
    assert not np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[1], outs[2])


# ------------------------------------------------------------- structure
def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _size(var):
    return int(np.prod(var.aval.shape)) if hasattr(var.aval, "shape") else 0


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_layer_scan_moves_no_pool_layer(kv_dtype):
    """The unified step's layer scan has no ``xs`` or ``ys`` leaf as large as
    a pool layer (the pool is carry), and no op in its body cuts, reshapes
    or stacks one. Its two scatters return the whole pool, in place."""
    nkv = 8
    tables, qstart, qlen, kvlen, seg, pos, ids, history = _step_inputs()
    pool_k, pool_v = _pool(nkv, kv_dtype, tables, history)
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    step = functools.partial(
        decode_mod._ragged_step_impl, n_steps=1, nh=NH, nkv=nkv, hd=HD,
        eps=1e-5, theta=10000.0, tied=False, decode_attn="pallas")
    jaxpr = jax.make_jaxpr(step)(
        _params(nkv), pool_k, pool_v, i32(tables), i32(ids), i32(seg),
        i32(pos), i32(qstart), i32(qlen), i32(kvlen), i32([1, 0, 0, 0]),
        jnp.zeros((R, 2), jnp.uint32), jnp.zeros((R,), jnp.float32),
        jnp.zeros((R,), jnp.int32), jnp.zeros((R,), jnp.int32),
        jnp.zeros((R,), jnp.int32), jnp.zeros((R, 2), jnp.uint32),
        i32([1, 0, 0, 0])).jaxpr
    layer = NB * BS * nkv * HD
    scans = [e for e in _walk(jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == L]
    assert len(scans) == 1
    scan = scans[0]
    n_fixed = scan.params["num_consts"] + scan.params["num_carry"]
    xs, ys = scan.invars[n_fixed:], scan.outvars[scan.params["num_carry"]:]
    assert xs and max(map(_size, xs)) < layer, [v.aval for v in xs]
    assert all(_size(v) < layer for v in ys), [v.aval for v in ys]
    # the pool is there, as carry
    carry = scan.invars[scan.params["num_consts"]:n_fixed]
    assert sum(_size(v) == L * layer for v in carry) == 2
    movers = ("reshape", "dynamic_slice", "dynamic_update_slice", "squeeze",
              "transpose", "copy", "concatenate")
    moved = [(e.primitive.name, v.aval)
             for e in _walk(scan.params["jaxpr"].jaxpr)
             if e.primitive.name in movers
             for v in e.outvars if _size(v) >= layer]
    assert not moved, moved
