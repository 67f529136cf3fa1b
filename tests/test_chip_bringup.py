"""What the sandbox can establish about the chip without one.

libtpu is installed here, so ``jax.experimental.topologies`` hands out
compile-only v5e devices under ``JAX_PLATFORMS=cpu`` and
``.lower(...).compile()`` runs the real XLA:TPU and Mosaic compilers. The
first class compiles every Pallas kernel on the default serving and training
paths for that topology; ``chip_smoke.py`` is what also RUNS them. The rest
pins the rules that keep a chip run from passing without the chip: no
interpret mode, jnp path or default peak on an unknown backend, and one
compile cache placed from outside.
"""
import functools
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from paddle_tpu.kernels import (dsa, flash_attention, gated_delta_rule,
                                moe_ffn, pallas_flash,
                                pallas_mla_ragged_attention,
                                pallas_paged_decode, pallas_ragged_attention,
                                selective_scan, ssd)
from paddle_tpu.parallel import mesh as mesh_mod
from paddle_tpu.profiler.metrics import peak_flops_per_chip
from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# (query heads, kv heads, head dim): llama_7b() MHA and the GQA variant
GEOMETRIES = [(32, 32, 128), (32, 8, 128)]


@functools.lru_cache(maxsize=None)
def _v5e_devices():
    """The four compile-only devices of a v5e 2x2 host, or the reason there
    are none (no libtpu, one without this topology, or another process
    holding libtpu's lock file)."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu").devices, ""
    except Exception as e:
        return None, f"{type(e).__name__}: {e}"


@pytest.fixture
def v5e_devices(monkeypatch):
    """Compile-only v5e devices, with the kernels told to compile (the
    default backend here is the CPU, where they would interpret)."""
    devices, why = _v5e_devices()
    if devices is None:
        pytest.skip(f"libtpu gives no v5e:2x2 topology: {why}")
    for mod in (pallas_flash, pallas_paged_decode, pallas_ragged_attention,
                pallas_mla_ragged_attention, moe_ffn, gated_delta_rule,
                selective_scan, dsa, ssd):
        monkeypatch.setattr(mod, "_interpret_mode", lambda: False)
    return devices


@pytest.fixture
def v5e(v5e_devices):
    """Abstract-array factory placed on one compile-only v5e device."""
    sharding = SingleDeviceSharding(v5e_devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _mosaic_calls(fn, *args):
    # the kernels' dots carry no precision of their own, and Mosaic rejects
    # bf16 operands under the "highest" that conftest.py sets for the fp32
    # oracles ("Bad lhs type"): compile at the precision the chip runs at
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("nh,nkv,hd", GEOMETRIES)
class TestMosaicCompilesDefaultPathKernels:
    NB, BS, R, MB = 64, 32, 8, 8        # pool blocks, block size, rows

    def test_ragged_paged_attention(self, v5e, nh, nkv, hd):
        pool = v5e((self.NB, self.BS, nkv, hd))
        row = v5e((self.R,), jnp.int32)
        n = _mosaic_calls(
            pallas_ragged_attention.ragged_paged_attention_pallas,
            v5e((72, nh, hd)), pool, pool,
            v5e((self.R, self.MB), jnp.int32), row, row, row)
        assert n == 1

    # what the serving cells run (benchmark/configs): 8 + 512 packed tokens,
    # 8 slots x 128 table entries, the pool PagedKVCache builds for them
    CELL_T, CELL_MB = 520, 128

    def test_ragged_paged_attention_at_the_serving_cells_shape(
            self, v5e, nh, nkv, hd):
        pool = v5e((self.R * self.CELL_MB, self.BS, nkv, hd))
        row = v5e((self.R,), jnp.int32)
        n = _mosaic_calls(
            pallas_ragged_attention.ragged_paged_attention_pallas,
            v5e((self.CELL_T, nh, hd)), pool, pool,
            v5e((self.R, self.CELL_MB), jnp.int32), row, row, row)
        assert n == 1

    def test_ragged_paged_attention_int8_pool(self, v5e, nh, nkv, hd):
        """The scale planes are fetched by the kernel block by block, like
        the data they scale: Mosaic must take that DMA too."""
        pool = v5e((self.NB, self.BS, nkv, hd), jnp.int8)
        plane = v5e((self.NB, self.BS, nkv), jnp.float32)
        row = v5e((self.R,), jnp.int32)

        def attend(q, pk, pv, tbl, qs, ql, kl, ks, vs):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tbl, qs, ql, kl, k_scale=ks, v_scale=vs)
        n = _mosaic_calls(
            attend, v5e((72, nh, hd)), pool, pool,
            v5e((self.R, self.MB), jnp.int32), row, row, row, plane, plane)
        assert n == 1

    def test_paged_decode_attention(self, v5e, nh, nkv, hd):
        pool = v5e((self.NB, self.BS, nkv, hd))
        n = _mosaic_calls(
            pallas_paged_decode.paged_decode_attention_pallas,
            v5e((self.R, nh, hd)), pool, pool,
            v5e((self.R, self.MB), jnp.int32), v5e((self.R,), jnp.int32))
        assert n == 1

    def test_flash_forward_and_backward(self, v5e, nh, nkv, hd):
        def loss(q, k, v):
            o = pallas_flash.flash_attention_pallas(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32))
        q, kv = v5e((1, 1024, nh, hd)), v5e((1, 1024, nkv, hd))
        n = _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
        assert n == 3                    # forward, dk/dv, dq


class TestMosaicCompilesTheRoutedFfn:
    """The routed FFN at OLMoE-1B-7B-0125's widths (hidden 2048, 64 experts
    of 1024, 8 a token): its three grouped matmuls are Mosaic calls with the
    tiling ``moe_ffn._tiling`` picks, at the unified step's packed buffer
    (24 slots + a 512-token chunk) and at a whole-prompt prefill."""
    H, E, I, K = 2048, 64, 1024, 8

    @pytest.mark.parametrize("rows", [536, 256, 8])
    def test_grouped_matmuls(self, v5e, rows):
        def ffn(h, r, wg, wu, wd, live):
            return moe_ffn.moe_ffn(h, r, wg, wu, wd, top_k=self.K, live=live)
        n = _mosaic_calls(
            ffn, v5e((rows, self.H)), v5e((self.H, self.E)),
            v5e((self.E, self.H, self.I)), v5e((self.E, self.H, self.I)),
            v5e((self.E, self.I, self.H)), v5e((rows,), jnp.bool_))
        assert n == 3                    # gate, up, down

    def test_a_layer_of_the_stack_in_place(self, v5e):
        """Inside the layer scan the weights are the stack over layers and
        the call reads its layer in place: no copy of a layer's 805 MB of
        experts appears beside the three kernels."""
        L, rows = 8, 536

        def ffn(h, r, wg, wu, wd, live, layer):
            return moe_ffn.moe_ffn(h, r, wg, wu, wd, top_k=self.K, live=live,
                                   layer=layer)
        args = (v5e((rows, self.H)), v5e((self.H, self.E)),
                v5e((L, self.E, self.H, self.I)),
                v5e((L, self.E, self.H, self.I)),
                v5e((L, self.E, self.I, self.H)), v5e((rows,), jnp.bool_),
                v5e((), jnp.int32))
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(ffn).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 3
        # temp holds the pair buffers (tens of MB), not a layer of experts
        assert compiled.memory_analysis().temp_size_in_bytes < 200 * 2 ** 20


class TestMosaicCompilesDeepseekV2:
    """DeepSeek-V2's two kernels at its published widths (128 heads over a
    latent of 512 + 64 stored in rows of 640 lanes; a router of 160 over a
    held 20 experts of 1536, 6 a token from 3 of 8 groups) and at the
    serving cell's shapes: 32 slots x 8192 tokens, a 512-token chunk."""
    NH, RANK, ROPE, W = 128, 512, 64, 640
    L, R, MB, BS = 8, 32, 256, 32

    @pytest.mark.parametrize("tokens", [544, 32])
    def test_latent_attention_over_the_stored_pool(self, v5e, tokens):
        i32 = jnp.int32

        def attend(q_lat, q_pe, pool, tables, qs, ql, kl, layer):
            return pallas_mla_ragged_attention.mla_ragged_attention_pallas(
                q_lat, q_pe, pool, tables, qs, ql, kl, scale=0.1,
                layer=layer)
        args = (v5e((tokens, self.NH, self.RANK)),
                v5e((tokens, self.NH, self.ROPE)),
                v5e((self.L, self.R * self.MB, self.BS, self.W)),
                v5e((self.R, self.MB), i32), v5e((self.R,), i32),
                v5e((self.R,), i32), v5e((self.R,), i32), v5e((), i32))
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(attend).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        # the pool (2.5 GiB) is read where it lies: no layer of it (320
        # MiB) is cut out or copied for the call
        assert compiled.memory_analysis().temp_size_in_bytes < 200 * 2 ** 20

    def test_latent_row_is_whole_lanes(self):
        assert pallas_mla_ragged_attention.latent_row_width(512, 64) == 640
        assert pallas_mla_ragged_attention.latent_row_width(32, 8) == 128

    @pytest.mark.parametrize("rows", [544, 256])
    def test_routed_ffn_with_a_share_of_the_experts(self, v5e, rows):
        H, E, held, width = 5120, 160, 20, 1536

        def ffn(h, r, wg, wu, wd, live, layer):
            return moe_ffn.moe_ffn(h, r, wg, wu, wd, top_k=6, live=live,
                                   layer=layer, n_group=8, topk_group=3,
                                   first_held=0, scale=16.0)
        n = _mosaic_calls(
            ffn, v5e((rows, H)), v5e((H, E)), v5e((7, held, H, width)),
            v5e((7, held, H, width)), v5e((7, held, width, H)),
            v5e((rows,), jnp.bool_), v5e((), jnp.int32))
        assert n == 3                    # gate, up, down over the held stack


class TestMosaicCompilesGlmSparseAttention:
    """GLM-5.2's sparse attention at its published widths (an indexer of 32
    heads of 128 over index keys of 128 lanes; 64 heads over a latent of 512
    + 64 stored in rows of 640 lanes; 2,048 selected) and at the serving
    cell's two packed sizes: 16 decode rows, and those beside one 512-token
    chunk, 16 slots x 20,480 tokens."""
    NH, RANK, ROPE, W, HI, D, TOPK = 64, 512, 64, 640, 32, 128, 2048
    L, LF, R, MB, BS = 6, 2, 16, 640, 32

    def _span(self, v5e):
        i32 = jnp.int32
        return (v5e((self.R, self.MB), i32), v5e((self.R,), i32),
                v5e((self.R,), i32), v5e((self.R,), i32), v5e((), i32))

    @pytest.mark.parametrize("tokens", [16, 528])
    def test_index_scores_selection_and_attention(self, v5e, tokens):
        def sparse(q_i, w_i, ipool, q_lat, q_pe, pool, tables, qs, ql, kl,
                   layer):
            scores = dsa.dsa_index_scores_pallas(
                q_i, w_i, ipool, tables, qs, ql, kl, layer=layer)
            mask = dsa.dsa_select(scores, self.TOPK)
            return dsa.dsa_attention_pallas(
                q_lat, q_pe, pool, tables, qs, ql, kl, dsa.selection_bias(
                    mask, self.NH, table_entries=self.MB,
                    block_size=self.BS), scale=0.1, layer=layer)
        nb = self.R * self.MB
        args = (v5e((tokens, self.HI, self.D)),
                v5e((tokens, self.HI), jnp.float32),
                v5e((self.LF, nb, self.BS, self.D)),
                v5e((tokens, self.NH, self.RANK)),
                v5e((tokens, self.NH, self.ROPE)),
                v5e((self.L, nb, self.BS, self.W))) + self._span(v5e)
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(sparse).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 2
        # the two pools (2.5 GiB) are read where they lie; a chunk's scores
        # and selection are 40 MiB each
        assert compiled.memory_analysis().temp_size_in_bytes < 400 * 2 ** 20


class TestMosaicCompilesOlmoHybrid:
    """Olmo-Hybrid-7B's kernels at its published widths (30 linear heads of
    96 x 192, a float32 state by slot; 30 full-attention heads of 128 over a
    pool row of 3,840) and at the serving cell's shapes: 32 slots x 2304
    tokens, 12 linear layers, a packed buffer of 32 + 512 rows."""
    H, DK, DV, R, LL, T = 30, 96, 192, 32, 12, 544
    # what the store holds is what the device lays out (PR 49): 2,211,840 B a
    # (layer, slot), no lane of padding (``[.., 30, 96, 192]`` lay in 256
    # lanes: a third more, and a third more for every decode row to copy)
    STORE_BYTES = 12 * 32 * 2211840

    def _store(self, v5e):
        return v5e((self.LL, self.R) + gated_delta_rule.state_shape(
            self.H, self.DK, self.DV), jnp.float32)

    def _aliases_the_store(self, compiled, temp_mib):
        """The store is aliased in and out at exactly its logical size: no
        layer of it (71 MB) is copied for the call, and nothing is padded."""
        mem = compiled.memory_analysis()
        assert self.STORE_BYTES == 4 * self.LL * self.R * self.H * self.DK \
            * self.DV
        assert mem.alias_size_in_bytes == self.STORE_BYTES
        assert mem.temp_size_in_bytes < temp_mib * 2 ** 20

    def test_the_decode_row_update_in_place(self, v5e):
        f32 = jnp.float32

        def update(q, k, v, g, b, st, live, fresh, layer):
            return gated_delta_rule.gdn_recurrent_update(
                q, k, v, g, b, st, layer=layer, live=live, fresh=fresh)
        args = (v5e((self.R, self.H, self.DK), f32),
                v5e((self.R, self.H, self.DK), f32),
                v5e((self.R, self.H, self.DV), f32),
                v5e((self.R, self.H), f32), v5e((self.R, self.H), f32),
                self._store(v5e), v5e((self.R,), jnp.bool_),
                v5e((self.R,), jnp.bool_), v5e((), jnp.int32))
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(update, donate_argnums=(5,)).lower(
                *args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        self._aliases_the_store(compiled, temp_mib=16)

    def test_the_chunked_scan_in_place(self, v5e):
        f32 = jnp.float32

        def scan(q, k, v, g, b, st, start, length, fresh, layer):
            return gated_delta_rule.gdn_chunk_scan(
                q, k, v, g, b, st, layer=layer, start=start, length=length,
                fresh=fresh)
        args = (v5e((self.T, self.H, self.DK), f32),
                v5e((self.T, self.H, self.DK), f32),
                v5e((self.T, self.H, self.DV)),
                v5e((self.T, self.H), f32), v5e((self.T, self.H), f32),
                self._store(v5e), v5e((self.R,), jnp.int32),
                v5e((self.R,), jnp.int32), v5e((self.R,), jnp.bool_),
                v5e((), jnp.int32))
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(scan, donate_argnums=(5,)).lower(
                *args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        self._aliases_the_store(compiled, temp_mib=64)

    def test_ragged_attention_at_30_heads(self, v5e):
        """The first head count that is no power of two, and the widest pool
        row: 30 planes of a head-major query, 128 tokens a query block, and
        no padding."""
        i32, hd, mb = jnp.int32, 128, 72

        def attend(q, pk, pv, tables, qs, ql, kl, layer):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tables, qs, ql, kl, layer=layer)
        pool = v5e((4, self.R * mb, 32, self.H * hd))
        n = _mosaic_calls(
            attend, v5e((self.T, self.H, hd)), pool, pool,
            v5e((self.R, mb), i32), v5e((self.R,), i32), v5e((self.R,), i32),
            v5e((self.R,), i32), v5e((), i32))
        assert n == 1
        assert pallas_ragged_attention.grid_params(
            jnp.bfloat16, 32, self.H * hd, mb, self.H, self.T,
            head_dim=hd) == dict(block_q=128 * self.H, pages=4,
                                 one_token=True)


class TestMosaicCompilesPhi4Flash:
    """Phi-4-mini-flash's kernels at its published widths (Mamba layers of
    5,120 channels x 16 states, a float32 state by slot; 40 wide queries over
    10 KV pairs of 128, a pool row of 1,280, window 512) and at the serving
    cell's shapes: 48 slots, 9 Mamba layers, a packed buffer of 48 + 512
    rows, rings of 33 blocks."""
    C, N, R, LL, T = 5120, 16, 48, 9, 560

    def _args(self, v5e, rows):
        f32 = jnp.float32
        return (v5e((rows, self.C), f32), v5e((rows, self.C), f32),
                v5e((rows, self.N), f32), v5e((rows, self.N), f32),
                v5e((self.N, self.C), f32),
                v5e((self.LL, self.R, self.N, self.C), f32))

    def _in_place(self, fn, args):
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        # the store (141 MiB) is aliased in and out, no layer of it copied
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes > 128 * 2 ** 20
        assert mem.temp_size_in_bytes < 16 * 2 ** 20

    def test_the_decode_row_update_in_place(self, v5e):
        def update(dt, u, b, c, a, st, live, fresh, layer):
            return selective_scan.ssm_recurrent_update(
                dt, u, b, c, a, st, layer=layer, live=live, fresh=fresh)
        self._in_place(update, self._args(v5e, self.R) + (
            v5e((self.R,), jnp.bool_), v5e((self.R,), jnp.bool_),
            v5e((), jnp.int32)))

    @pytest.mark.parametrize("rows", [560, 48], ids=["chunk", "decode_only"])
    def test_the_chunk_scan_in_place(self, v5e, rows):
        def scan(dt, u, b, c, a, st, start, length, fresh, layer):
            return selective_scan.ssm_chunk_scan(
                dt, u, b, c, a, st, layer=layer, start=start, length=length,
                fresh=fresh, min_span=2)
        self._in_place(scan, self._args(v5e, rows) + (
            v5e((self.R,), jnp.int32), v5e((self.R,), jnp.int32),
            v5e((self.R,), jnp.bool_), v5e((), jnp.int32)))

    def test_windowed_ragged_attention_over_the_rings(self, v5e):
        i32, hd, heads, ring, mb = jnp.int32, 128, 40, 33, 256

        def attend(q, wk, wv, tables, qs, ql, kl, layer):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, wk, wv, tables, qs, ql, kl, layer=layer, window=512)
        store = v5e((8, self.R * ring, 32, 10 * hd))
        n = _mosaic_calls(
            attend, v5e((self.T, heads, hd)), store, store,
            v5e((self.R, mb), i32), v5e((self.R,), i32), v5e((self.R,), i32),
            v5e((self.R,), i32), v5e((), i32))
        assert n == 1
        assert pallas_ragged_attention.grid_params(
            jnp.bfloat16, 32, 10 * hd, mb, heads, self.T,
            head_dim=hd) == dict(block_q=96 * heads, pages=6, one_token=True)


class TestMosaicCompilesJamba:
    """Jamba2-3B's kernels at its serving cell's shapes: ONE store of all 26
    Mamba layers' states (16 slots x ``[16, 5120]`` float32, 136 MiB), a
    packed buffer of 16 + 512 rows or of 16; 20 query heads on one KV head
    of 128: a group that is no power of two, a pool row of 128 lanes, tables
    of 1,024 entries."""
    C, N, R, LL = 5120, 16, 16, 26

    @pytest.mark.parametrize("rows", [528, 16], ids=["chunk", "decode_only"])
    def test_the_scan_kernels_in_place(self, v5e, rows):
        f32, i32 = jnp.float32, jnp.int32

        def both(dt, u, b, c, a, st, start, length, live, fresh, layer):
            y1, st = selective_scan.ssm_recurrent_update(
                dt[:self.R], u[:self.R], b[:self.R], c[:self.R], a, st,
                layer=layer, live=live, fresh=fresh)
            yn, st = selective_scan.ssm_chunk_scan(
                dt, u, b, c, a, st, layer=layer, start=start, length=length,
                fresh=fresh, min_span=2)
            return y1, yn, st
        args = (v5e((rows, self.C), f32), v5e((rows, self.C), f32),
                v5e((rows, self.N), f32), v5e((rows, self.N), f32),
                v5e((self.N, self.C), f32),
                v5e((self.LL, self.R, self.N, self.C), f32),
                v5e((self.R,), i32), v5e((self.R,), i32),
                v5e((self.R,), jnp.bool_), v5e((self.R,), jnp.bool_),
                v5e((), i32))
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(both, donate_argnums=(5,)).lower(
                *args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 2
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes > 128 * 2 ** 20
        assert mem.temp_size_in_bytes < 16 * 2 ** 20

    @pytest.mark.parametrize("rows", [528, 16], ids=["chunk", "decode_only"])
    def test_ragged_attention_at_twenty_heads_on_one(self, v5e, rows):
        i32, hd, heads, mb = jnp.int32, 128, 20, 1024

        def attend(q, pk, pv, tables, qs, ql, kl, layer):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tables, qs, ql, kl, layer=layer)
        pool = v5e((2, self.R * mb, 32, hd))
        n = _mosaic_calls(
            attend, v5e((rows, heads, hd)), pool, pool,
            v5e((self.R, mb), i32), v5e((self.R,), i32), v5e((self.R,), i32),
            v5e((self.R,), i32), v5e((), i32))
        assert n == 1
        # the accumulator's 192 tokens a query block (3,840 rows of the one
        # plane, walked in 8 row chunks of 480; a buffer of 16 is one block of
        # 320), 256 keys an update, and a span of one token on its own tile
        # of 80 rows: 4 tokens in 5 row tiles
        assert pallas_ragged_attention.grid_params(
            jnp.bfloat16, 32, hd, mb, heads, rows, head_dim=hd) == dict(
                block_q=min(192, rows) * heads, pages=8, one_token=True)
        assert pallas_ragged_attention._row_chunks(192 * heads) \
            == [(c0, 480) for c0 in range(0, 3840, 480)]


class TestMosaicCompilesTheSpanUpdate:
    """The general walk's own online-softmax update (PR 53: a row's ``m`` on
    every lane, ``l`` by lane) at the packed size of a chunk step of the
    cells that differ in how it lowers: Jamba2-3B's tall plane in row chunks
    of 480 (20 / 1 / 128), Mistral's eight planes of 512 rows (32 / 8 /
    128), Phi-4-mini-flash's window of 512 over its rings (20 / 10 / 128 as
    the kernel sees its head pairs: the window's edge goes through the same
    update), each over a bfloat16 and an int8 pool, whose head windows are
    float32 by the time they reach it."""

    @pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
    @pytest.mark.parametrize("heads,kv_heads,slots,mb,window", [
        (20, 1, 16, 1024, None), (32, 8, 8, 128, None),
        (20, 10, 48, 256, 512)], ids=["20-1", "32-8", "20-10-window"])
    def test_lowers_at_a_chunk_steps_packed_size(self, v5e, heads, kv_heads,
                                                 slots, mb, window,
                                                 pool_dtype):
        i32, f32, hd, bs = jnp.int32, jnp.float32, 128, 32
        quantized = pool_dtype == "int8"

        def attend(q, pk, pv, tables, qs, ql, kl, layer, *scales):
            planes = dict(zip(("k_scale", "v_scale"), scales))
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tables, qs, ql, kl, layer=layer, window=window,
                **planes)
        pool = v5e((2, slots * mb, bs, kv_heads * hd), jnp.dtype(pool_dtype))
        plane = v5e((2, slots * mb, bs, kv_heads), f32)
        n = _mosaic_calls(
            attend, v5e((slots + 512, heads, hd)), pool, pool,
            v5e((slots, mb), i32), v5e((slots,), i32), v5e((slots,), i32),
            v5e((slots,), i32), v5e((), i32), *([plane] * 2 * quantized))
        assert n == 1
        # keys an update: 256, fewer at a wide pool row (192 at 10 KV heads;
        # a one-byte pool counts at four bytes a value), never under 128:
        # one, one and a half or two lane tiles of scores a row
        tiling = pallas_ragged_attention.grid_params(
            jnp.dtype(pool_dtype), bs, kv_heads * hd, mb, heads,
            slots + 512, head_dim=hd)
        assert tiling["one_token"] and tiling["pages"] * bs in (128, 192, 256)


class TestMosaicCompilesNemotronH:
    """Nemotron-3-Nano's kernels at its published widths (Mamba-2 blocks of
    64 heads x 64 channels in 8 groups, a state of 128, float32 by slot; two
    matrices an expert at 1,856, no whole number of lanes) and at the serving
    cell's shapes: 32 slots, 23 Mamba-2 blocks, a packed buffer of 32 + 512
    rows, 16 held experts a layer."""
    H, P, G, N, R, LL, T = 64, 64, 8, 128, 32, 23, 544

    def _args(self, v5e, rows):
        f32 = jnp.float32
        return (v5e((rows, self.H, self.P), f32), v5e((rows, self.H), f32),
                v5e((self.H,), f32), v5e((rows, self.G, self.N), f32),
                v5e((rows, self.G, self.N), f32),
                v5e((self.LL, self.R) + ssd.state_shape(
                    self.H, self.P, self.G, self.N), f32))

    def _in_place(self, fn, args):
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        # the store (1.44 GiB) is aliased in and out, no layer of it copied
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes > 1.4 * 2 ** 30
        assert mem.temp_size_in_bytes < 32 * 2 ** 20

    def test_the_decode_row_update_in_place(self, v5e):
        def update(x, dt, a, b, c, st, live, fresh, layer):
            return ssd.ssd_recurrent_update(
                x, dt, a, b, c, st, layer=layer, live=live, fresh=fresh)
        self._in_place(update, self._args(v5e, self.R) + (
            v5e((self.R,), jnp.bool_), v5e((self.R,), jnp.bool_),
            v5e((), jnp.int32)))

    def test_the_chunk_scan_in_place(self, v5e):
        def scan(x, dt, a, b, c, st, start, length, fresh, layer):
            return ssd.ssd_chunk_scan(
                x, dt, a, b, c, st, layer=layer, start=start, length=length,
                fresh=fresh)
        self._in_place(scan, self._args(v5e, self.T) + (
            v5e((self.R,), jnp.int32), v5e((self.R,), jnp.int32),
            v5e((self.R,), jnp.bool_), v5e((), jnp.int32)))

    @pytest.mark.parametrize("rows", [544, 32], ids=["chunk", "decode_only"])
    def test_ragged_attention_at_sixteen_heads_a_kv_head(self, v5e, rows):
        """The six attention blocks' 32 query heads on 2 KV heads of 128 at
        the cell's shapes (32 slots x 192 table entries): the accumulator's
        128 tokens a query block, each plane's 2,048 rows walked in 4 row
        chunks of 512."""
        i32, hd, heads, mb = jnp.int32, 128, 32, 192

        def attend(q, pk, pv, tables, qs, ql, kl, layer):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tables, qs, ql, kl, layer=layer)
        pool = v5e((6, self.R * mb, 32, 2 * hd))
        n = _mosaic_calls(
            attend, v5e((rows, heads, hd)), pool, pool,
            v5e((self.R, mb), i32), v5e((self.R,), i32), v5e((self.R,), i32),
            v5e((self.R,), i32), v5e((), i32))
        assert n == 1
        assert pallas_ragged_attention.grid_params(
            jnp.bfloat16, 32, 2 * hd, mb, heads, rows, head_dim=hd) == dict(
                block_q=min(128, rows) * heads, pages=8, one_token=True)
        assert pallas_ragged_attention._row_chunks(128 * 16) \
            == [(c0, 512) for c0 in range(0, 2048, 512)]

    def test_two_matrix_experts_read_their_stacks_in_place(self, v5e):
        """``w_up`` by output unit: no operand's minor dimension is the
        expert width, and neither stack (3.4 GiB each) is copied."""
        hid, wid, exp = 2688, 1856, 16

        def ffn(h, router, bias, w_up, w_down, layer):
            return moe_ffn.moe_ffn(
                h, router, None, w_up, w_down, layer=layer, top_k=6,
                renormalize=True, first_held=0, scale=2.5,
                router_bias=bias)[0]
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(ffn).lower(
                v5e((1, 32, hid)), v5e((hid, 128)),
                v5e((128,), jnp.float32), v5e((self.LL, exp, wid, hid)),
                v5e((self.LL, exp, wid, hid)), v5e((), jnp.int32)).compile()
        assert compiled.as_text().count("tpu_custom_call") == 2
        assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20


class TestMosaicCompilesQwen3Next:
    """Qwen3-Next's kernels at its published widths (Gated DeltaNet at 32
    value heads on 16 key heads of 128 x 128, a float32 state ``[128, 4096]``
    by slot: a key width of whole lane tiles, which pads nothing; 16 query
    heads on 2 KV heads of 256; 64 held experts of 512 under a router of 512,
    10 a token) and at the serving cell's shapes: 128 slots x 4,096, 9 linear
    layers, a packed buffer of 128 + 512 rows or of 128."""
    H, HK, DK, DV, R, LL, T = 32, 16, 128, 128, 128, 9, 640

    def _store(self, v5e):
        return v5e((self.LL, self.R) + gated_delta_rule.state_shape(
            self.H, self.DK, self.DV), jnp.float32)

    def _in_place(self, fn, args):
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(fn, donate_argnums=(5,)).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        # the store (2.25 GiB) is aliased in and out at its logical size: no
        # layer of it (256 MiB) is copied, q and k are not repeated to 32
        # heads (a chunk's would be 2 x 640 x 32 x 128 x 4 B = 20 MiB)
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == 4 * self.LL * self.R * self.H \
            * self.DK * self.DV
        assert mem.temp_size_in_bytes < 48 * 2 ** 20

    def test_the_decode_row_update_in_place(self, v5e):
        f32 = jnp.float32

        def update(q, k, v, g, b, st, live, fresh, layer):
            return gated_delta_rule.gdn_recurrent_update(
                q, k, v, g, b, st, layer=layer, live=live, fresh=fresh)
        self._in_place(update, (
            v5e((self.R, self.HK, self.DK), f32),
            v5e((self.R, self.HK, self.DK), f32),
            v5e((self.R, self.H, self.DV), f32), v5e((self.R, self.H), f32),
            v5e((self.R, self.H), f32), self._store(v5e),
            v5e((self.R,), jnp.bool_), v5e((self.R,), jnp.bool_),
            v5e((), jnp.int32)))

    def test_the_chunked_scan_in_place(self, v5e):
        f32 = jnp.float32

        def scan(q, k, v, g, b, st, start, length, fresh, layer):
            return gated_delta_rule.gdn_chunk_scan(
                q, k, v, g, b, st, layer=layer, start=start, length=length,
                fresh=fresh)
        self._in_place(scan, (
            v5e((self.T, self.HK, self.DK), f32),
            v5e((self.T, self.HK, self.DK), f32),
            v5e((self.T, self.H, self.DV)), v5e((self.T, self.H), f32),
            v5e((self.T, self.H), f32), self._store(v5e),
            v5e((self.R,), jnp.int32), v5e((self.R,), jnp.int32),
            v5e((self.R,), jnp.bool_), v5e((), jnp.int32)))
        # four value heads and their two key heads a grid step
        assert gated_delta_rule._scan_heads(self.H, self.DV, 2) == 4

    @pytest.mark.parametrize("rows", [640, 128], ids=["chunk", "decode_only"])
    def test_ragged_attention_at_heads_of_256(self, v5e, rows):
        """The three full layers' 16 query heads on 2 KV heads of 256 at the
        cell's shapes (128 slots x 128 table entries): the first head wider
        than 128."""
        i32, hd, heads, mb = jnp.int32, 256, 16, 128

        def attend(q, pk, pv, tables, qs, ql, kl, layer):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tables, qs, ql, kl, layer=layer)
        pool = v5e((3, self.R * mb, 32, 2 * hd))
        n = _mosaic_calls(
            attend, v5e((rows, heads, hd)), pool, pool,
            v5e((self.R, mb), i32), v5e((self.R,), i32), v5e((self.R,), i32),
            v5e((self.R,), i32), v5e((), i32))
        assert n == 1

    @pytest.mark.parametrize("rows", [640, 128, 8192],
                             ids=["chunk", "decode_only", "whole_prompt"])
    def test_sixty_four_held_experts_read_their_stacks_in_place(self, v5e,
                                                                 rows):
        """Three matrices an expert at 512, 64 of a router's 512 held: three
        grouped matmuls on a buffer of 1,664 or 384 pair slots (``m`` of the
        pairs a share takes, ISSUE 57: one body, run in passes), and none of
        the three stacks ``[3, 64, ...]`` (384 MiB each) is copied into the
        loop. The decode step goes back by the product over the slots, the
        other two by the gather by pair, pick-major: at a whole-prompt
        program's 8,192 rows the buffer is 20,480 slots and the temporaries
        451 MiB where a slot a pick took 835."""
        hid, wid, exp, places = 2048, 512, 64, 3

        def ffn(h, router, w_gate, w_up, w_down, layer):
            return moe_ffn.moe_ffn(
                h, router, w_gate, w_up, w_down, layer=layer, top_k=10,
                renormalize=True, first_held=0)[0]
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(ffn).lower(
                v5e((1, rows, hid)), v5e((hid, 512)),
                v5e((places, exp, hid, wid)), v5e((places, exp, hid, wid)),
                v5e((places, exp, wid, hid)), v5e((), jnp.int32)).compile()
        cap = moe_ffn._capacity(rows * 10, exp, 512)
        assert cap == {640: 1664, 128: 384, 8192: 20480}[rows]
        assert (cap <= moe_ffn.PRODUCT_SLOTS) == (rows == 128)
        assert compiled.as_text().count("tpu_custom_call") == 3
        assert compiled.memory_analysis().temp_size_in_bytes < (
            512 if rows == 8192 else 128) * 2 ** 20


class TestMosaicCompilesMiMoV2Flash:
    """MiMo-V2-Flash's kernels at its published widths (64 query heads, keys
    192 wide and values 128: a KV head's key window starts at half a lane tile
    for every odd head; 4 KV heads in the pool, 8 in the rings, under a window
    of 128 with a sink a head; 16 held experts of 2,048 under a sigmoid router
    of 256, 8 a token, nothing beside them) and at the serving cell's shapes:
    32 slots x 1,024 table entries, rings of 21 blocks, a packed buffer of 32
    + 512 rows or of 32."""
    H, DK, DV, R, MB = 64, 192, 128, 32, 1024

    def _attend(self, v5e, rows, nkv, layers, blocks, window):
        i32 = jnp.int32

        def attend(q, pk, pv, tables, qs, ql, kl, sink, layer):
            return pallas_ragged_attention.ragged_paged_attention_pallas(
                q, pk, pv, tables, qs, ql, kl, layer=layer, window=window,
                sink=sink if window else None)
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(attend).lower(
                v5e((rows, self.H, self.DK)),
                v5e((layers, blocks, 32, nkv * self.DK)),
                v5e((layers, blocks, 32, nkv * self.DV)),
                v5e((self.R, self.MB), i32), v5e((self.R,), i32),
                v5e((self.R,), i32), v5e((self.R,), i32),
                v5e((self.H,), jnp.float32), v5e((), i32)).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        # the output is a VALUE wide; neither store is copied or padded
        assert compiled.memory_analysis().temp_size_in_bytes < 32 * 2 ** 20
        return compiled

    @pytest.mark.parametrize("rows", [544, 32], ids=["chunk", "decode_only"])
    def test_the_pool_at_keys_of_192_under_values_of_128(self, v5e, rows):
        """The two full layers: 64 heads on 4 KV heads, a pool of 32 x 1,024
        blocks whose K side is 768 lanes and whose V side 512."""
        self._attend(v5e, rows, 4, 2, self.R * self.MB, None)

    @pytest.mark.parametrize("rows", [544, 32], ids=["chunk", "decode_only"])
    def test_the_rings_under_the_window_with_the_sink(self, v5e, rows):
        """The five window layers: 64 heads on 8 KV heads over the rings as a
        pool of 32 x 21 blocks (1,536 | 1,024 lanes), a window of 128, the
        sink one more column of a head's softmax."""
        self._attend(v5e, rows, 8, 5, self.R * 21, 128)

    @pytest.mark.parametrize("rows", [544, 32], ids=["chunk", "decode_only"])
    def test_sixteen_held_experts_under_the_sigmoid_router(self, v5e, rows):
        """Three matrices an expert at 2,048, 16 of a router's 256 held, one
        place of the period: three grouped matmuls, and none of the three
        stacks ``[1, 16, ...]`` (256 MiB each) is copied."""
        hid, wid, exp = 4096, 2048, 16

        def ffn(h, router, bias, w_gate, w_up, w_down, layer):
            return moe_ffn.moe_ffn(
                h, router, w_gate, w_up, w_down, layer=layer, top_k=8,
                renormalize=True, first_held=0, router_bias=bias)[0]
        with jax.default_matmul_precision("default"):
            compiled = jax.jit(ffn).lower(
                v5e((1, rows, hid)), v5e((hid, 256)),
                v5e((256,), jnp.float32), v5e((1, exp, hid, wid)),
                v5e((1, exp, hid, wid)), v5e((1, exp, wid, hid)),
                v5e((), jnp.int32)).compile()
        assert compiled.as_text().count("tpu_custom_call") == 3
        assert compiled.memory_analysis().temp_size_in_bytes < 128 * 2 ** 20


class TestUnifiedStepLeavesThePoolInPlace:
    """The unified serving step, small, compiled for the described v5e: in
    the optimised HLO nothing but the in-place row scatter has a result as
    large as one layer of the KV pool. The copies this keeps from coming back
    (a layer cut out of the scanned pool for the Mosaic call, re-laid-out for
    it, stacked back) were 24-68 % of a serving step's device time."""
    L, H, NH, NKV, HD, FFN, V = 4, 512, 8, 4, 128, 1024, 2048
    R, MB, BS, T = 8, 32, 32, 136       # pool layer: 256 blocks, 8 MiB

    def _compile(self, v5e):
        from paddle_tpu.serving import decode
        from paddle_tpu.serving.block_manager import BlockManager
        L, H, kd = self.L, self.H, self.NKV * self.HD
        params = dict(
            embed=v5e((self.V, H)), wq=v5e((L, H, self.NH * self.HD)),
            wk=v5e((L, H, kd)), wv=v5e((L, H, kd)),
            wo=v5e((L, self.NH * self.HD, H)), w_gate=v5e((L, H, self.FFN)),
            w_up=v5e((L, H, self.FFN)), w_down=v5e((L, self.FFN, H)),
            input_ln=v5e((L, H)), post_ln=v5e((L, H)),
            final_norm=v5e((H,)), lm_head=v5e((H, self.V)))
        pool = v5e(BlockManager.pool_shape(L, self.R * self.MB, self.BS,
                                           self.NKV, self.HD))
        i32 = jnp.int32
        tok, row = v5e((self.T,), i32), v5e((self.R,), i32)
        step = decode.build_ragged_step_fn(
            n_steps=1, nh=self.NH, nkv=self.NKV, hd=self.HD, eps=1e-5,
            theta=1e4, tied=False, decode_attn="pallas", donate=True)
        with jax.default_matmul_precision("default"):
            return step.lower(
                params, pool, pool, v5e((self.R, self.MB), i32), tok, tok,
                tok, row, row, row, row, v5e((self.R, 2), jnp.uint32),
                v5e((self.R,), jnp.float32), row, row, row,
                v5e((self.R, 2), jnp.uint32), row).compile()

    def test_no_op_but_the_scatter_returns_a_pool_layer(self, v5e):
        import re
        compiled = self._compile(v5e)
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1
        layer = self.R * self.MB * self.BS * self.NKV * self.HD
        # computation name -> its text, to see what a fusion wraps
        bodies = dict(re.findall(
            r"^(?:ENTRY )?(%[\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
            re.S | re.M))
        inst = re.compile(
            r"^\s*(?:ROOT )?(%[\w.\-]+) = \w+\[([\d,]*)\]\S* "
            r"([\w\-]+)\((.*)$", re.M)
        passive = {"parameter", "get-tuple-element", "tuple", "while",
                   "bitcast", "scatter"}
        moved, scatters = [], 0
        for name, dims, op, rest in inst.findall(text):
            if not dims or np.prod([int(d) for d in dims.split(",")]) < layer:
                continue
            if op == "fusion":
                called = re.search(r"calls=(%[\w.\-]+)", rest).group(1)
                if " scatter(" in bodies[called]:
                    scatters += 1
                    continue
            if op not in passive:
                moved.append((op, name, dims))
        assert not moved, moved
        assert scatters == 2                # K and V, in the layer loop
        # and the step's temp holds no copy of a layer
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer


class TestFlashKernelUnderTheHybridMesh:
    """GSPMD cannot partition a Mosaic custom call, so under a mesh the flash
    kernel runs in a shard_map (``flash_attention.shard_over_mesh``): batch
    over the data axes, heads over ``mp``. At the parent commit the four-chip
    train step failed to lower: "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"."""
    DEGREES = {"dp": 1, "pp": 1, "sharding": 2, "sep": 1, "ep": 1, "mp": 2}
    SPEC = PartitionSpec(("dp", "sharding"), None, "mp", None)

    @pytest.fixture
    def use_kernel(self, monkeypatch):
        monkeypatch.setattr(flash_attention, "_use_pallas", lambda s: True)
        monkeypatch.setitem(mesh_mod._STATE, "mesh", None)  # restored after

    @staticmethod
    def _loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v, True) ** 2)

    def test_agrees_with_reference_on_the_cpu_mesh(self, use_kernel):
        mesh = mesh_mod.set_mesh(mesh_mod.build_mesh(
            self.DEGREES, devices=jax.devices()[:4]))
        rng = np.random.RandomState(0)
        put = lambda *shape: jax.device_put(       # noqa: E731
            jnp.asarray(rng.randn(*shape), jnp.float32) * 0.3,
            NamedSharding(mesh, self.SPEC))
        q, k, v = put(4, 128, 4, 32), put(4, 128, 2, 32), put(4, 128, 2, 32)
        grad = jax.value_and_grad(self._loss(flash_attention.attention),
                                  argnums=(0, 1, 2))
        loss, grads = jax.jit(grad)(q, k, v)
        ref_loss, ref_grads = jax.value_and_grad(
            self._loss(flash_attention._ref_attention),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
        for g, r in zip(grads, ref_grads):
            np.testing.assert_allclose(g, r, atol=1e-5)
        assert grads[0].sharding.spec == self.SPEC      # stayed sharded

    def test_lowers_for_the_four_chip_topology(self, v5e_devices,
                                               use_kernel):
        mesh = mesh_mod.set_mesh(mesh_mod.build_mesh(
            self.DEGREES, devices=v5e_devices))
        arr = lambda heads: jax.ShapeDtypeStruct(   # noqa: E731
            (4, 1024, heads, 128), jnp.bfloat16,
            sharding=NamedSharding(mesh, self.SPEC))
        n = _mosaic_calls(
            jax.grad(self._loss(flash_attention.attention),
                     argnums=(0, 1, 2)), arr(32), arr(8), arr(8))
        assert n == 3


class TestNoFallbackHidesTheDevice:
    def test_interpret_mode_by_backend(self, monkeypatch):
        assert pallas_flash._interpret_mode() is True       # cpu: tests
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert pallas_flash._interpret_mode() is False
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            pallas_flash._interpret_mode()

    def test_use_pallas_by_backend(self, monkeypatch):
        assert flash_attention._use_pallas(2048) is False   # cpu: jnp path
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert flash_attention._use_pallas(2048) is True
        assert flash_attention._use_pallas(128) is False    # short: by design
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            flash_attention._use_pallas(2048)

    def test_compiler_params_are_the_real_class(self):
        from jax.experimental.pallas import tpu as pltpu
        p = pallas_flash._cparams(("parallel", "arbitrary"))
        assert isinstance(p, pltpu.CompilerParams)

    def test_peak_flops_raises_on_unknown_device(self):
        v5e_dev = types.SimpleNamespace(device_kind="TPU v5 lite")
        assert peak_flops_per_chip(v5e_dev) == 197e12
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            peak_flops_per_chip(
                types.SimpleNamespace(device_kind="TPU v9 imaginary"))
        with pytest.raises(ValueError):
            peak_flops_per_chip()        # the CPU backend has no peak


class TestCompileCachePlacement:
    def test_cache_dir_resolution(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        assert compile_cache.cache_dir() == "/somewhere/else"

    @pytest.mark.parametrize("placed", [True, False])
    def test_enable_sets_no_other_directory(self, tmp_path, placed):
        """In a fresh process: with the variable set JAX keeps the cache
        there and enable() names no other; without it the cache resolves
        inside the checkout."""
        env = {k: v for k, v in os.environ.items()
               if k != compile_cache.ENV_VAR}
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        want = os.path.join(REPO, ".jax_cache")
        if placed:
            want = env[compile_cache.ENV_VAR] = str(tmp_path / "cache")
        code = ("import jax\n"
                "from paddle_tpu.utils import compile_cache\n"
                "stats = compile_cache.enable()\n"
                "print(jax.config.jax_compilation_cache_dir)\n"
                "print(stats.snapshot()['cache_dir'])\n")
        p = subprocess.run([sys.executable, "-c", code], env=env, timeout=120,
                           capture_output=True, text=True)
        assert p.returncode == 0, p.stderr[-500:]
        assert p.stdout.split() == [want, want]
