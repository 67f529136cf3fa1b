"""Collective overlap (engine ``collective_overlap=True``, README
"Collective overlap") and the launch census that shows its schedule:
the TP per-layer all-reduce pair overlaps with compute as a chunked
reduce-scatter/all-gather schedule. The load-bearing properties:

- **Transparency**: overlapped TP=2 streams equal BOTH the TP=1 and the
  non-overlapped TP=2 baselines, greedy AND seeded-sampled, single-tick
  and multi-tick.
- **Launch census**: a jaxpr census of the multi-tick while body counts
  the scanned layer stack's ``pallas_call``s (>= num_layers a tick),
  surfaced through ``/debug/profile``; the census's own rules are
  tested where they are written (``tests/test_cost_observatory.py``).
- **Compile-once**: the ``ov`` tag keys the overlapped schedule apart in
  a shared jit cache.
- **Exact accounting**: the overlapped schedule moves the same wire
  payload — ``serving_collective_bytes_total{dtype}`` stays exact to
  the byte in both wire dtypes.
"""
import pytest

from paddle_tpu.profiler.cost import CostObservatory
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.server.gateway import ServingGateway

import serving_support
from serving_support import (BS, CHUNK, S_MAX, SLOTS, engine as _engine,
                             prompt as _prompt)


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA: nkv=2 < nh=4


@pytest.fixture(scope="module")
def spec_error(model):
    with pytest.raises(ValueError,
                       match="incompatible with spec_decode") as ei:
        _engine(model, decode_ticks=4, spec_decode=True, spec_k=2)
    return str(ei.value)


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 5)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


#: greedy shorts, a seeded-sampled row, and a long prompt that chunks
#: (40 > CHUNK)
def _traffic():
    return [_req(1), _req(2, n=10),
            _req(3, temperature=0.9, top_k=5, seed=123),
            _req(4, n=40, max_new_tokens=4)]


def _run_once(model, **kw):
    """One cold pass of the traffic; returns (streams, engine)."""
    eng = _engine(model, prefix_cache=True, **kw)
    return [o.tolist() for o in eng.generate(_traffic())], eng


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

    def test_overlap_composes_with_multitick(self, model):
        """tp=2 x collective_overlap x decode_ticks=4 streams equal the
        single-chip decode_ticks=4 baseline, compile-once inclusive of
        the (tp2, dtype, ov) key tail."""
        base, _ = _run_once(model, decode_ticks=4)
        full, e2 = _run_once(model, decode_ticks=4, tp=2,
                             collective_overlap=True)
        assert full == base
        assert e2.decode_compilations() == 1
        assert e2.collective_overlap


# ------------------------------------------------------------ launch census
class TestLaunchCensus:
    def test_census_pins_scanned_layers(self, model):
        """Census the multi-tick while body (= launches per decode
        tick): the scanned layer stack holds >= num_layers
        pallas_calls. The census rides the observatory export, so
        ``/debug/profile`` program entries carry it."""
        co = CostObservatory()
        # (the trie only so that the program is the multi-tick test's own)
        eng = _engine(model, prefix_cache=True, decode_ticks=4)
        eng.cost = co
        eng.generate([_req(17, max_new_tokens=6)])
        # export surfaces the census on the program entry — the
        # /debug/profile document is built from this export
        ent = [p for p in co.export()["programs"]
               if "mtick" in str(p.get("program"))]
        assert ent and ent[0].get("census") is not None
        cs = co.snapshot_full()["censuses"]
        keys = [k for k in cs if "mtick" in str(k)]
        assert keys, list(cs)
        body = cs[keys[0]]["loop_bodies"][-1]
        assert body["pallas_calls"] >= model.config.num_hidden_layers

    def test_profile_doc_surfaces_census(self, model):
        """A gateway-owned observatory flows the census into
        ``/debug/profile``: program entries carry the launch counts."""
        gw = ServingGateway(_engine(model, prefix_cache=True),
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
    # sweep (test_cost_observatory) pins the ov tag site, and the
    # compile-once asserts on every default rep pin the key behavior
    def test_jit_keys_carry_ov_tag(self, model):
        """The ov marker rides the tp tag, while a default engine's keys
        carry neither."""
        jit = {}    # its own: the assertions are on what each engine ADDS
        e1 = _engine(model, jit_cache=jit)
        e1.generate([_req(11, max_new_tokens=2)])
        keys1 = set(jit)
        assert all("ov" not in k for k in keys1)
        assert e1.decode_compilations() == 1
        e3 = _engine(model, jit_cache=jit, tp=2, collective_overlap=True)
        e3.generate([_req(11, max_new_tokens=2)])
        keys3 = set(jit) - keys1
        assert keys3
        decode3 = [k for k in keys3 if "tp2" in k]
        assert decode3 and all("ov" in k for k in decode3)
        assert e3.decode_compilations() == 1

    @pytest.mark.parametrize("knob", [
        "prefix_cache", "prefill_chunk", "kv_dtype", "quantize_weights",
        "quantize_activations", "tp", "collective_overlap",
        "host_tier_bytes", "priority_classes"])
    def test_multitick_spec_error_enumerates_knobs(self, spec_error, knob):
        """The --decode-ticks x spec_decode error names every
        compatible knob, a knob a case, so the CLI failure is
        self-documenting."""
        assert knob in spec_error

    def test_overlap_requires_tp(self, model):
        with pytest.raises(ValueError, match="requires tp > 1"):
            _engine(model, collective_overlap=True)

    def test_fleet_geometry_ends_in_overlap(self, model):
        """collective_overlap closes the fleet geometry tuple — same
        memory-note discipline as the tp/kv8 tags."""
        from paddle_tpu.serving.fleet import EngineFleet
        # the model is the process's: other files' fleets hang their
        # programs on it, so read what this fleet adds and pop nothing
        jits = model.__dict__.setdefault("_serving_jit_fleet", {})
        before = set(jits)
        fleet = EngineFleet(model, replicas=1, num_slots=SLOTS,
                            max_seq_len=S_MAX, prefill_chunk=CHUNK,
                            prefix_block_size=BS, tp=2,
                            collective_overlap=True, start=False)
        (geom,) = set(jits) - before
        assert geom[-3:] == (2, "fp", True)
        assert fleet.replicas[0].gateway.engine.collective_overlap is True
        fleet.shutdown(drain=False, timeout=5)
