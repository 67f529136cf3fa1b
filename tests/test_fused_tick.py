"""One-kernel decode (engine ``fused_tick=True`` +
``collective_overlap=True``, README "One-kernel decode"): the whole
per-token decode tick — every layer's norms, projections, paged
table-indirect attention, SwiGLU, the final norm/head/sampling — runs
as ONE ``pallas_call`` with the layer loop as a grid dimension, and
the TP per-layer all-reduce pair overlaps with compute as a chunked
reduce-scatter/all-gather schedule. The load-bearing properties:

- **Transparency**: fused streams are BYTE-IDENTICAL to the scanned
  baseline — greedy AND seeded-sampled, cold/hit/chunked, int8/fp8 KV,
  multi-tick, TP=2, across preempt/restore — and overlapped TP=2
  streams equal BOTH the TP=1 and the non-overlapped TP=2 baselines.
- **Launch census**: the claim is PINNED structurally, not vibes — a
  jaxpr census of the multi-tick while body counts exactly 1
  ``pallas_call`` fused vs >= num_layers scanned, surfaced through
  ``/debug/profile``.
- **Compile-once**: ``decode_compilations() == 1`` INCLUSIVE of the
  ``fk`` tag (and ``fk`` x ``tpN`` x ``kv8f``/``a8``); the ``ov`` tag
  keys the overlapped schedule apart in a shared jit cache.
- **Exact accounting**: the overlapped schedule moves the same wire
  payload — ``serving_collective_bytes_total{dtype}`` stays exact to
  the byte in both wire dtypes.
"""
import pytest

from paddle_tpu.profiler.cost import CostObservatory
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from serving_support import (BS, CHUNK, S_MAX, SLOTS, clone as _clone,
                             engine as _engine, prompt as _prompt)


@pytest.fixture(scope="module")
def model():
    # llama_tiny defaults decode_attention="pallas": fused_tick takes
    # the TRUE mega-kernel path (single pallas_call, interpret on CPU)
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


@pytest.fixture(scope="module")
def jnp_model():
    # the jnp-attention oracle route: fused_decode_tick dispatches to
    # the reference replay, byte-identical by construction — pinned
    # here so BOTH dispatch arms stay covered
    return serving_support.model("llama", seed=33, decode_attention="jnp")


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 5)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


#: the hit/miss/chunked matrix: greedy shorts, a seeded-sampled row,
#: and a long prompt that chunks (40 > CHUNK)
def _traffic():
    return [_req(1), _req(2, n=10),
            _req(3, temperature=0.9, top_k=5, seed=123),
            _req(4, n=40, max_new_tokens=4)]


#: the rows of ``_run_matrix``'s result, in order
MATRIX_ROWS = [f"{p}-{r}" for p in ("cold", "hit")
               for r in ("greedy", "greedy10", "sampled", "chunked")]


def _run_matrix(model, **kw):
    """Two passes of the traffic (pass 2 = trie hits on pass 1's
    donated chains) through one engine; returns (streams, engine)."""
    eng = _engine(model, prefix_cache=True, **kw)
    outs = [o.tolist() for o in eng.generate(_traffic())]
    outs += [o.tolist() for o in
             eng.generate([_clone(r) for r in _traffic()])]
    return outs, eng


def _run_once(model, **kw):
    """One cold pass of the traffic; returns (streams, engine)."""
    eng = _engine(model, prefix_cache=True, **kw)
    return [o.tolist() for o in eng.generate(_traffic())], eng


# ----------------------------------------------------------- transparency
@pytest.fixture(scope="module")
def fused_matrix(model):
    """The scanned and the fused engine after the matrix each, run once for
    the module: ``(base streams, scanned engine, fused streams, fused
    engine)``."""
    return _run_matrix(model) + _run_matrix(model, fused_tick=True)


class TestFusedByteIdentity:
    @pytest.mark.parametrize("row", MATRIX_ROWS)
    def test_fused_matrix_byte_identical_and_compile_once(self, fused_matrix,
                                                          row):
        """THE tentpole pin: the single-pallas_call fused tick streams
        byte-for-byte equal to the scanned baseline — greedy AND
        seeded-sampled, cold/hit/chunked, a case a row — with
        ``decode_compilations() == 1`` on BOTH engines (one shared jit
        cache; the fk tag keys the fused trace apart, so neither
        engine's pin sees the other's programs)."""
        base, e1, fused, e2 = fused_matrix
        i = MATRIX_ROWS.index(row)
        assert len(fused) == len(base) == len(MATRIX_ROWS)
        assert fused[i] == base[i]
        assert e1.decode_compilations() == 2
        assert e2.decode_compilations() == 2
        assert e2.prefill_compilations() >= 1
        assert e2.fused_tick is True and e1.fused_tick is False

    @pytest.mark.slow  # 5 s + fixture: the jnp-attention oracle arm
    # (870s cap); the default matrix rep drives the TRUE kernel, and
    # the oracle is the construction both routes are pinned against
    def test_fused_oracle_route_byte_identical(self, jnp_model):
        """decode_attention="jnp" routes fused_decode_tick to the
        reference replay (the oracle arm): streams still equal the
        scanned baseline and the compile pin still holds."""
        base, _ = _run_once(jnp_model)
        fused, e2 = _run_once(jnp_model, fused_tick=True)
        assert fused == base
        assert e2.decode_compilations() == 1

    def test_fused_multitick_byte_identical(self, model):
        """The fused program slots into the multi-tick while body:
        fused x decode_ticks=4 equals scanned x decode_ticks=4 (which
        is itself pinned equal to single-tick)."""
        base, _ = _run_once(model, decode_ticks=4)
        fused, e2 = _run_once(model, decode_ticks=4,
                              fused_tick=True)
        assert fused == base
        assert e2.decode_compilations() == 1

    @pytest.mark.slow  # 7 s quant duplicate (870s cap): the matrix +
    # multi-tick reps above run the fused kernel by default; the kv8f
    # x fk compile pin also rides the AST key-discipline sweep
    def test_fused_fp8_kv_byte_identical(self, model):
        """fp8 KV dequantizes IN-KERNEL on the fused path (no
        host-side dequant launch): streams equal the scanned fp8-KV
        engine, compile-once inclusive of kv8f + fk."""
        base, _ = _run_matrix(model, kv_dtype="fp8")
        fused, e2 = _run_matrix(model, kv_dtype="fp8",
                                fused_tick=True)
        assert fused == base
        assert e2.decode_compilations() == 1

    @pytest.mark.slow  # 12 s matrix duplicate: the fp8 rep above runs
    # by default (870s cap); int8 adds the scale-plane dequant arm
    def test_fused_int8_kv_byte_identical(self, model):
        base, _ = _run_matrix(model, kv_dtype="int8")
        fused, e2 = _run_matrix(model, kv_dtype="int8",
                                fused_tick=True)
        assert fused == base
        assert e2.decode_compilations() == 1

    @pytest.mark.slow  # 14 s matrix duplicate: the overlap tests below
    # run fused x tp2 by default (870s cap)
    def test_fused_tp2_byte_identical(self, model):
        """Sharded fused engine (the TP oracle route — in-kernel
        collectives are the remote-DMA follow-on) equals the TP=1
        scanned baseline."""
        base, _ = _run_matrix(model)
        tp2, e2 = _run_matrix(model, tp=2, fused_tick=True)
        assert tp2 == base
        assert e2.decode_compilations() == 1

    def test_fused_preempt_restore_byte_identical(self, model):
        """Mid-decode evict + restore on a fused engine: the chain
        donates to the trie, recompute readmits as a trie hit through
        the fused program, and the continuation equals the
        uninterrupted scanned baseline."""
        reqs = _traffic()
        base = [o.tolist() for o in
                _engine(model, prefix_cache=True)
                .generate([_clone(r) for r in reqs])]
        eng = _engine(model, prefix_cache=True,
                      fused_tick=True)
        seqs = [eng.submit(_clone(r)) for r in reqs]
        for _ in range(3):
            eng.step()
        victim = next(s for s in seqs if s.status == "running")
        assert eng.evict(victim)
        eng.restore(victim)
        while eng.has_work():
            eng.step()
        assert [list(s.output_ids()) for s in seqs] == base
        assert eng.decode_compilations() == 2


# ------------------------------------------------- compute/collective overlap
class TestCollectiveOverlap:
    @pytest.mark.parametrize("dtype", [
        "fp",
        # 10 s wire-dtype duplicate (870s cap): fp is the default rep;
        # the int8 wire format itself is pinned by test_tp's ledger
        pytest.param("int8", marks=pytest.mark.slow)])
    def test_overlap_byte_identical_and_ledger_exact(self, model, dtype):
        """The overlap acceptance pin, both wire dtypes: overlapped
        TP=2 streams equal BOTH the TP=1 baseline and the
        non-overlapped TP=2 engine (greedy AND seeded-sampled), the
        ``serving_collective_bytes_total{dtype}`` ledger is byte-equal
        to the non-overlapped run's (whose exactness test_tp pins
        against the closed-form wire model), and the jaxpr census
        proves the schedule really changed — the overlapped decode
        program carries MORE collective eqns (chunked ppermute
        reduce-scatter/all-gather) than the plain all-reduce pair."""
        base, _ = _run_once(model)
        co_p, co_o = CostObservatory(), CostObservatory()
        e_p = _engine(model, prefix_cache=True, tp=2,
                      collective_dtype=dtype)
        e_p.cost = co_p
        plain = [o.tolist() for o in e_p.generate(_traffic())]
        e_o = _engine(model, prefix_cache=True, tp=2,
                      collective_dtype=dtype, collective_overlap=True)
        e_o.cost = co_o
        over = [o.tolist() for o in e_o.generate(_traffic())]
        assert plain == base
        assert over == base
        assert e_p.decode_compilations() == 2
        assert e_o.decode_compilations() == 2
        assert e_o.collective_overlap is True
        # ledger exact to the byte: identical op/byte totals, nonzero
        led_p = co_p.snapshot_full()["collectives"]
        led_o = co_o.snapshot_full()["collectives"]
        assert led_o == led_p
        assert led_o[dtype]["bytes"] > 0 and led_o[dtype]["ops"] > 0
        # the knob is not a no-op: census the decode programs
        cen_p = [c for k, c in co_p.snapshot_full()["censuses"].items()
                 if "ragged" in str(k) or "mtick" in str(k)]
        cen_o = [c for k, c in co_o.snapshot_full()["censuses"].items()
                 if "ragged" in str(k) or "mtick" in str(k)]
        assert cen_p and cen_o
        assert cen_o[0]["collectives"] > cen_p[0]["collectives"]

    @pytest.mark.slow  # 9 s composition duplicate (870s cap): the
    # overlap[fp] + fused-multitick reps above cover both arms default
    def test_overlap_composes_with_fused_multitick(self, model):
        """Full stack: fused_tick x tp=2 x collective_overlap x
        decode_ticks=4 streams equal the scanned single-chip
        decode_ticks=4 baseline, compile-once inclusive of the
        (tp2, dtype, ov) + fk key tail."""
        base, _ = _run_once(model, decode_ticks=4)
        full, e2 = _run_once(model, decode_ticks=4, tp=2,
                             fused_tick=True, collective_overlap=True)
        assert full == base
        assert e2.decode_compilations() == 1
        assert e2.fused_tick and e2.collective_overlap


# ------------------------------------------------------------ launch census
class TestLaunchCensus:
    def test_census_pins_fused_one_launch_scanned_layers(self, model):
        """The structural pin behind the headline: census the
        multi-tick while body (= launches per decode tick). Scanned:
        >= num_layers pallas_calls. Fused: EXACTLY 1. The census rides
        the observatory export, so ``/debug/profile`` program entries
        carry it."""
        L = model.config.num_hidden_layers

        def census_of(co, frag):
            cs = co.snapshot_full()["censuses"]
            keys = [k for k in cs if frag in str(k)]
            assert keys, (frag, list(cs))
            return cs[keys[0]]

        co_s, co_f = CostObservatory(), CostObservatory()
        for co, kw in ((co_s, {}), (co_f, dict(fused_tick=True))):
            eng = _engine(model, decode_ticks=4, **kw)
            eng.cost = co
            eng.generate([_req(17, max_new_tokens=6)])
            # export surfaces the census on the program entry — the
            # /debug/profile document is built from this export
            ent = [p for p in co.export()["programs"]
                   if "mtick" in str(p.get("program"))]
            assert ent and ent[0].get("census") is not None
        scanned = census_of(co_s, "mtick")["loop_bodies"][-1]
        fused = census_of(co_f, "mtick")["loop_bodies"][-1]
        assert scanned["pallas_calls"] >= L
        assert fused["pallas_calls"] == 1

    def test_profile_doc_surfaces_census(self, model):
        """A gateway-owned observatory flows the census into
        ``/debug/profile``: program entries carry the launch counts."""
        gw = ServingGateway(
            _engine(model, prefix_cache=True,
                    fused_tick=True),
            max_queue=8, start=False)
        st = gw.submit(_req(19))
        gw.start()
        st.result()
        doc = gw.profile_doc()
        cens = [p["census"] for p in doc["programs"]
                if p.get("census") is not None]
        assert cens
        assert all({"pallas_calls", "collectives",
                    "loop_bodies"} <= set(c) for c in cens)
        gw.shutdown(drain=True, timeout=30)


# ------------------------------------------------------ jit keys / validation
class TestJitKeysAndValidation:
    @pytest.mark.slow  # 6 s key-shape duplicate (870s cap): the AST
    # sweep (test_cost_observatory) pins the fk/ov tag sites, and the
    # compile-once asserts on every default rep pin the key behavior
    def test_jit_keys_carry_fk_and_ov_tags(self, model):
        """The fk tag joins the decode jit keys LAST (after kv8f/a8/
        tpN) and the ov marker rides the tp tag — while knobs-off keys
        stay byte-identical to the pre-fused spelling (banked baselines
        can't have drifted)."""
        jit = {}    # its own: the assertions are on what each engine ADDS
        e1 = _engine(model, jit_cache=jit)
        e1.generate([_req(11, max_new_tokens=2)])
        keys1 = set(jit)
        assert all("fk" not in k and "ov" not in k for k in keys1)
        e2 = _engine(model, jit_cache=jit, fused_tick=True)
        e2.generate([_req(11, max_new_tokens=2)])
        keys2 = set(jit) - keys1
        assert keys2 and all(k[-1] == "fk" for k in keys2)
        assert e1.decode_compilations() == 1
        assert e2.decode_compilations() == 1
        e3 = _engine(model, jit_cache=jit, tp=2, collective_overlap=True)
        e3.generate([_req(11, max_new_tokens=2)])
        keys3 = set(jit) - keys1 - keys2
        assert keys3
        decode3 = [k for k in keys3 if "tp2" in k]
        assert decode3 and all("ov" in k for k in decode3)
        assert e3.decode_compilations() == 1

    @pytest.mark.slow  # 8 s geometry duplicate (870s cap): every
    # default rep asserts decode_compilations()==1 on its own geometry
    def test_compile_once_fused_quant_tp_geometries(self, model):
        """The acceptance's hardest compile pin: fk x tp2 x kv8f and
        fk x tp2 x w8+a8 each trace their decode program exactly
        once."""
        e1 = _engine(model, tp=2, kv_dtype="fp8", fused_tick=True)
        e1.generate([_req(21, max_new_tokens=3)])
        assert e1.decode_compilations() == 1
        e2 = _engine(model, tp=2, quantize_weights=True,
                     quantize_activations=True, fused_tick=True)
        e2.generate([_req(22, max_new_tokens=3)])
        assert e2.decode_compilations() == 1

    def test_fused_spec_error_enumerates_knobs(self, model):
        """fused x spec is rejected with the COMPATIBLE knob set
        spelled out (the error is documentation)."""
        with pytest.raises(ValueError,
                           match="fused_tick composes with") as ei:
            _engine(model, fused_tick=True, spec_decode=True, spec_k=2)
        msg = str(ei.value)
        for knob in ("prefix_cache", "decode_ticks", "kv_dtype", "tp",
                     "collective_overlap", "priority_classes"):
            assert knob in msg

    def test_multitick_spec_error_enumerates_knobs(self, model):
        """The --decode-ticks x spec_decode error names every
        compatible knob — fused_tick and collective_overlap
        included — so the CLI failure is self-documenting."""
        with pytest.raises(ValueError,
                           match="incompatible with spec_decode") as ei:
            _engine(model, decode_ticks=4, spec_decode=True, spec_k=2)
        msg = str(ei.value)
        for knob in ("fused_tick", "collective_overlap",
                     "prefix_cache", "kv_dtype", "tp",
                     "priority_classes"):
            assert knob in msg

    def test_overlap_requires_tp(self, model):
        with pytest.raises(ValueError, match="requires tp > 1"):
            _engine(model, collective_overlap=True)

    def test_fleet_geometry_grows_fused_and_overlap(self, model):
        """(fused_tick, collective_overlap) join the fleet geometry
        tuple — same memory-note discipline as the tp/kv8 tags."""
        from paddle_tpu.serving.fleet import EngineFleet
        # the model is the process's: other files' fleets hang their
        # programs on it, so read what this fleet adds and pop nothing
        jits = model.__dict__.setdefault("_serving_jit_fleet", {})
        before = set(jits)
        fleet = EngineFleet(model, replicas=1, num_slots=SLOTS,
                            max_seq_len=S_MAX, prefill_chunk=CHUNK,
                            prefix_block_size=BS, fused_tick=True,
                            start=False)
        (geom,) = set(jits) - before
        assert geom[-2:] == (True, False)
        eng = fleet.replicas[0].gateway.engine
        assert eng.fused_tick is True and eng.collective_overlap is False
        fleet.shutdown(drain=False, timeout=5)
