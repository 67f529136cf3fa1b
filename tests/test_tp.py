"""Multi-chip tensor-parallel serving (engine ``tp=N``, README
"Tensor-parallel serving"): every serving program shard_map'd over a
heads-sharded CPU mesh (conftest forces 8 virtual devices) with the
paged KV pool partitioned per shard. The load-bearing properties:

- **Transparency**: TP=2 (and TP=4) token streams are BYTE-IDENTICAL
  to the single-chip baseline — greedy AND seeded-sampled, across the
  hit/miss/chunked matrix and the spec / multi-tick / int8-KV engine
  variants — and ``decode_compilations() == 1`` holds INCLUSIVE of the
  sharded geometry (the tp tag keys the shard_map trace apart in a
  shared jit cache).
- **Exact collective accounting**: the per-layer all-reduce pair is
  the only cross-chip traffic; its wire bytes are counted shape-exactly
  (``serving_collective_bytes_total{dtype}``) and host-boundary h2d/d2h
  bytes are LOGICAL — never double-counted across mesh shards (the
  cost-observatory satellite).
- **EQuARX int8 collectives**: ``collective_dtype="int8"`` cuts wire
  bytes >= 3x with MEASURED (not assumed) divergence, deterministic
  under replay.
- **Lifecycle**: displacement/restore and crash recovery carry the
  per-shard pools correctly — recompute is byte-identical on a sharded
  engine, chaos matrix loses nothing.
"""
import numpy as np
import pytest

from paddle_tpu.profiler.cost import CostObservatory
from paddle_tpu.quantization import (collective_wire_bytes,
                                     quantized_psum_int8)
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from serving_support import (BS, CHUNK, S_MAX, SLOTS, clone as _clone,
                             engine as _engine, prompt as _prompt)
from test_metrics_prom import parse_prometheus


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


@pytest.fixture(scope="module")
def mha_model():
    return serving_support.model("llama", seed=34,
                                 num_key_value_heads=4)    # tp=4-able


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 5)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


#: the hit/miss/chunked matrix: greedy shorts, a seeded-sampled row,
#: and a long prompt that chunks (40 > CHUNK)
def _traffic():
    return [_req(1), _req(2, n=10),
            _req(3, temperature=0.9, top_k=5, seed=123),
            _req(4, n=40, max_new_tokens=4)]


def _run_matrix(model, **kw):
    """Two passes of the traffic (pass 2 = trie hits on pass 1's
    donated chains) through one engine; returns (streams, engine)."""
    eng = _engine(model, prefix_cache=True, **kw)
    outs = [o.tolist() for o in eng.generate(_traffic())]
    outs += [o.tolist() for o in
             eng.generate([_clone(r) for r in _traffic()])]
    return outs, eng


# ----------------------------------------------------------- transparency
class TestTPByteIdentity:
    @pytest.mark.slow  # 14 s matrix duplicate: tp4/spec/multitick/int8 byte-
    # identity reps below run by default (870s cap)
    def test_tp2_matrix_byte_identical_and_compile_once(self, model):
        """THE acceptance pin: TP=2 streams equal the single-chip
        baseline byte-for-byte — greedy AND seeded-sampled, cold/hit/
        chunked — with ``decode_compilations() == 1`` on BOTH engines
        (they share one jit cache; the tp tag keys the sharded traces
        apart, so neither engine's pin sees the other's programs)."""
        base, e1 = _run_matrix(model, tp=1)
        tp2, e2 = _run_matrix(model, tp=2)
        assert tp2 == base
        assert e1.decode_compilations() == 1
        assert e2.decode_compilations() == 1
        # prefill side stays bounded and tag-isolated the same way
        assert e2.prefill_compilations() >= 1
        assert e2.tp == 2 and e1.tp == 1
        assert e1.collective_dtype == "fp"

    @pytest.mark.slow
    def test_tp4_byte_identical(self, mha_model):
        """TP=1 ≡ TP=4 on the MHA tiny model (nkv=4 divides 4)."""
        base, _ = _run_matrix(mha_model, tp=1)
        tp4, e4 = _run_matrix(mha_model, tp=4)
        assert tp4 == base
        assert e4.decode_compilations() == 1

    @pytest.mark.slow
    def test_tp_spec_decode_byte_identical(self, model):
        """The spec-verify program rides ``_packed_span_forward`` too:
        a sharded speculative engine streams byte-identically to the
        single-chip speculative engine (which is itself pinned equal to
        non-spec), compile-once inclusive of the spec+tp geometry."""
        base, _ = _run_matrix(model, tp=1, spec_decode=True, spec_k=3)
        tp2, e2 = _run_matrix(model, tp=2, spec_decode=True, spec_k=3)
        assert tp2 == base
        assert e2.decode_compilations() == 1

    @pytest.mark.slow
    def test_tp_multitick_byte_identical(self, model):
        """The multi-tick while_loop tail shards like the scan tail:
        decode_ticks=4 on TP=2 equals decode_ticks=4 on one chip."""
        base, _ = _run_matrix(model, tp=1, decode_ticks=4)
        tp2, e2 = _run_matrix(model, tp=2, decode_ticks=4)
        assert tp2 == base
        assert e2.decode_compilations() == 1

    @pytest.mark.slow
    def test_tp_int8_kv_byte_identical(self, model):
        """int8 KV pools shard on the same head axis (scale planes
        ride along): TP=2 int8-KV streams equal single-chip int8-KV."""
        base, _ = _run_matrix(model, tp=1, kv_dtype="int8")
        tp2, e2 = _run_matrix(model, tp=2, kv_dtype="int8")
        assert tp2 == base
        assert e2.decode_compilations() == 1
        # the pool really is partitioned: data AND scale planes carry
        # the head-sharded NamedSharding
        spec = e2.cache.pool.k.sharding.spec
        assert "tp" in tuple(spec)
        assert "tp" in tuple(e2.cache.pool.k_scale.sharding.spec)


# ---------------------------------------------------- collective accounting
class TestCollectiveAccounting:
    def _one_req_run(self, model, tp, collective_dtype="fp"):
        co = CostObservatory()
        eng = _engine(model, tp=tp, collective_dtype=collective_dtype)
        eng.cost = co
        # 14 tokens <= prefill_chunk: ONE-SHOT cold prefill, bucket 16
        eng.generate([GenerationRequest(
            prompt=(np.arange(14, dtype=np.int32) % 100),
            max_new_tokens=5)])
        return co, eng

    def test_ledger_exact_and_h2d_parity(self, model):
        """Closed-form collective-byte pin + the cost-observatory
        satellite: one 14-token prompt, 5 greedy tokens, no chunking =
        one cold prefill launch (bucket 16) + four single-tick unified
        steps (the padded packed buffer at its decode-only size, the
        slots' rows rounded up to 8) — 2L all-reduces each, bytes
        equal to the shared wire model TO THE BYTE. And the h2d/d2h
        boundary ledger of the tp=2 run equals the tp=1 run's exactly:
        per-shard arg/result leaves count LOGICAL bytes once, never
        once per mesh device."""
        c = model.config
        L, hm = c.num_hidden_layers, c.hidden_size
        co1, _ = self._one_req_run(model, 1)
        co2, e2 = self._one_req_run(model, 2)
        # tp=1: no mesh, no wire — explicit zero, empty ledger
        assert co1.collectives == {}
        assert co1.collective_bytes("fp") == 0
        want = 2 * L * collective_wire_bytes(16, hm, 2, "fp")
        assert e2.step_rows[0] == 8 < e2._token_budget
        want += 4 * 2 * L * collective_wire_bytes(8, hm, 2, "fp")
        assert co2.collective_bytes("fp") == want
        assert co2.collectives["fp"]["ops"] == 2 * L * 5
        # the satellite pin: logical-once boundary accounting — the
        # sharded engine's h2d/d2h totals match the single-chip run
        assert co2.totals["h2d_bytes"] == co1.totals["h2d_bytes"]
        assert co2.totals["d2h_bytes"] == co1.totals["d2h_bytes"]

    def test_int8_collective_cuts_wire_bytes_3x(self, model):
        """Same workload, wire dtype swapped: op counts match and the
        byte ratio shows the EQuARX cut (>= 3x; scale overhead is
        4·tp/hidden). Streams replay deterministically."""
        co_fp, _ = self._one_req_run(model, 2, "fp")
        co_q, _ = self._one_req_run(model, 2, "int8")
        assert co_q.collectives["int8"]["ops"] == \
            co_fp.collectives["fp"]["ops"]
        ratio = co_fp.collective_bytes("fp") \
            / co_q.collective_bytes("int8")
        assert ratio >= 3.0

    def test_wire_model_units(self):
        """The shared wire model: tp<=1 is free; fp prices the ring
        reduce-scatter+all-gather on the fp payload; int8 prices the
        int8 payload plus one fp32 scale per (row, chunk) per phase."""
        assert collective_wire_bytes(10, 64, 1, "fp") == 0
        rows, hm, tp = 6, 64, 2
        assert collective_wire_bytes(rows, hm, tp, "fp") == \
            2 * rows * hm * 4 * (tp - 1) // tp
        assert collective_wire_bytes(rows, hm, tp, "int8") == \
            2 * (rows * hm + rows * tp * 4) * (tp - 1) // tp
        # >= 3x whenever hidden dominates the scale overhead
        assert (collective_wire_bytes(8, 64, 2, "fp")
                / collective_wire_bytes(8, 64, 2, "int8")) > 3.0

    def test_metrics_and_profile_surface(self, model):
        """``serving_collective_bytes_total{dtype}`` scrapes from a
        sharded gateway (fp > 0, int8 an explicit 0 — both series
        exist), and ``/debug/profile`` carries the per-layer
        collective-bytes column."""
        def factory():
            return _engine(model, tp=2)

        gw = ServingGateway(factory(), engine_factory=factory,
                            max_queue=8, start=False)
        st = gw.submit(_req(7))
        gw.start()
        st.result()
        fams = parse_prometheus(gw.registry.render())
        s = fams["serving_collective_bytes_total"]["samples"]
        assert s[("serving_collective_bytes_total",
                  (("dtype", "fp"),))] > 0
        assert s[("serving_collective_bytes_total",
                  (("dtype", "int8"),))] == 0
        doc = gw.profile_doc()
        assert doc["collectives"]["tp"] == 2
        fp = doc["collectives"]["per_dtype"]["fp"]
        assert fp["bytes"] > 0 and fp["bytes_per_layer"] > 0
        assert fp["bytes"] == pytest.approx(
            fp["bytes_per_layer"] * model.config.num_hidden_layers)
        gw.shutdown(drain=True, timeout=30)


# ----------------------------------------------------- quantized all-reduce
class TestQuantizedPsum:
    def test_roundtrip_vs_fp_psum(self):
        """Under shard_map on a 2-device mesh the quantized all-reduce
        approximates psum within the double-quantization error bound,
        is exact on exactly-representable payloads, and preserves
        zeros exactly."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.serving.decode import _tp_mesh

        mesh = _tp_mesh(2)
        x = np.random.RandomState(0).randn(2, 6, 64).astype(np.float32)

        def body(v):
            loc = v[jax.lax.axis_index("tp")]
            return (quantized_psum_int8(loc, "tp", 2),
                    jax.lax.psum(loc, "tp"))

        q, exact = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(x)
        err = np.max(np.abs(np.asarray(q) - np.asarray(exact)))
        # two absmax/127 roundings: bound ~2 * amax/127 per element sum
        bound = 2.5 * float(np.max(np.abs(x))) * 2 / 127.0
        assert err <= bound
        # all-zero payloads stay exactly zero (scale-0 rule)
        z, _ = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(np.zeros_like(x))
        assert np.all(np.asarray(z) == 0.0)

    def test_exact_on_representable_payload(self):
        """A payload whose every quantization step is lossless —
        integer values with amax exactly 127 in every (row, chunk) on
        one shard, zeros on the other (the scale-0 rule) — survives
        BOTH wire phases bit-exactly: pins the dequant math itself,
        not just an error bound."""
        import jax
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.serving.decode import _tp_mesh

        mesh = _tp_mesh(2)
        rng = np.random.RandomState(1)
        x = np.zeros((2, 4, 64), np.float32)
        x[0] = rng.randint(-127, 128, (4, 64)).astype(np.float32)
        x[0, :, 0] = 127.0      # amax 127 in chunk 0 of every row
        x[0, :, 32] = 127.0     # ...and in chunk 1 (H/tp = 32)

        def body(v):
            loc = v[jax.lax.axis_index("tp")]
            return (quantized_psum_int8(loc, "tp", 2),
                    jax.lax.psum(loc, "tp"))

        q, exact = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False))(x)
        assert np.array_equal(np.asarray(q), np.asarray(exact))


# -------------------------------------------------------------- validation
class TestTPValidation:
    def test_rejects_bad_configs(self, model):
        with pytest.raises(ValueError, match="tp must be >= 1"):
            _engine(model, tp=0)
        with pytest.raises(ValueError, match="collective_dtype"):
            _engine(model, tp=2, collective_dtype="fp8")
        with pytest.raises(ValueError, match="must divide"):
            _engine(model, tp=3)       # nh=4, nkv=2: 3 divides neither
        from paddle_tpu.serving.decode import _tp_mesh
        with pytest.raises(ValueError, match="XLA_FLAGS"):
            _tp_mesh(64)               # conftest forces 8 devices

    def test_tp1_int8_collectives_are_inert(self, model):
        """tp=1 has no mesh and no wire: the effective collective
        dtype normalizes to fp (banners/geometry report what runs)."""
        eng = _engine(model, tp=1, collective_dtype="int8")
        assert eng.collective_dtype == "fp"

    def test_jit_keys_carry_the_tp_tag(self, model):
        """The TP degree joins the jit key: after a sharded run every
        program key of the tp=2 engine carries the ("tp2", dtype) tail
        while tp=1 keys stay byte-identical to the pre-TP spelling (no
        tag — banked baselines can't have drifted)."""
        # programs of its own (jnp path, the cheapest to lower): the
        # assertions are on what each engine ADDS to the cache
        model = serving_support.model("llama", seed=33,
                                      decode_attention="jnp")
        jit = {}
        e1 = _engine(model, tp=1, jit_cache=jit)
        e1.generate([_req(11, max_new_tokens=2)])
        keys1 = set(jit)
        assert all("tp2" not in k for k in keys1)
        e2 = _engine(model, tp=2, jit_cache=jit)
        e2.generate([_req(11, max_new_tokens=2)])
        keys2 = set(jit) - keys1
        assert keys2 and all(k[-2:] == ("tp2", "fp") for k in keys2)
        assert e1.decode_compilations() == 1
        assert e2.decode_compilations() == 1

    def test_fleet_geometry_grows_tp(self, model):
        """Replicas with different TP degrees get isolated jit-cache
        dicts: (tp, collective_dtype) joins the fleet geometry tuple —
        same memory-note discipline as the kv8/w8 tags."""
        from paddle_tpu.serving.fleet import EngineFleet
        # the model is the process's: other files' fleets hang their
        # programs on it, so read what this fleet adds and pop nothing
        jits = model.__dict__.setdefault("_serving_jit_fleet", {})
        before = set(jits)
        fleet = EngineFleet(model, replicas=1, num_slots=SLOTS,
                            max_seq_len=S_MAX, prefill_chunk=CHUNK,
                            prefix_block_size=BS, tp=2,
                            collective_dtype="int8", start=False)
        (geom,) = set(jits) - before
        # tail of the geometry tuple: (tp, collective_dtype,
        # collective_overlap)
        assert geom[-3:] == (2, "int8", False)
        assert fleet.replicas[0].gateway.engine.tp == 2
        fleet.shutdown(drain=False, timeout=5)


# ------------------------------------------------------------- lifecycle
@pytest.mark.slow
class TestTPLifecycle:
    def test_displace_restore_carries_sharded_pool(self, model):
        """Mid-decode evict + restore on a sharded engine: the chain
        donates to the trie (per-shard blocks and all), recompute
        readmits as a trie hit, and the continuation is byte-identical
        to the uninterrupted single-chip baseline."""
        reqs = _traffic()
        base = [o.tolist() for o in
                _engine(model, tp=1, prefix_cache=True).generate(
                    [_clone(r) for r in reqs])]
        eng = _engine(model, tp=2, prefix_cache=True)
        seqs = [eng.submit(_clone(r)) for r in reqs]
        for _ in range(3):
            eng.step()
        victim = next(s for s in seqs if s.status == "running")
        assert eng.evict(victim)
        eng.restore(victim)
        while eng.has_work():
            eng.step()
        assert [list(s.output_ids()) for s in seqs] == base
        assert eng.decode_compilations() == 1

    def test_chaos_matrix_zero_lost_on_sharded_engine(self, model):
        """transient -> fatal -> nan against a tp=2 supervised gateway:
        the nan fault REALLY poisons the SHARDED pool before crashing,
        so byte-identical streams prove recovery rebuilt the mesh
        engine and recomputed per-shard KV from host token state.
        0 requests lost."""
        reqs = _traffic()
        base = [o.tolist() for o in
                _engine(model, tp=2, prefix_cache=True).generate(
                    [_clone(r) for r in reqs])]

        def factory():
            return _engine(model, tp=2, prefix_cache=True)

        plan = FaultPlan().at_step(1, "transient") \
                          .at_step(3, "fatal").at_step(6, "nan")
        gw = ServingGateway(factory(), engine_factory=factory,
                            fault_hook=plan, max_queue=16, start=False)
        streams = [gw.submit(_clone(r)) for r in reqs]
        gw.start()
        outs = [st.result() for st in streams]
        assert [ids.tolist() for ids, _ in outs] == base
        assert gw.restarts == 2
        assert gw.engine.decode_compilations() == 1
        gw.shutdown(drain=True, timeout=30)
