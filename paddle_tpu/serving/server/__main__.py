"""CLI entry point: ``python -m paddle_tpu.serving.server``.

Stands up a model behind the async gateway and serves
OpenAI-style completions over HTTP until SIGINT/SIGTERM, then drains
gracefully (in-flight requests finish; new ones get 503).

The ``tiny`` preset is the CPU-runnable config; ``350m`` and
``llama7b-8of32`` (Llama-2-7B widths, depth cut to 8 of 32 layers) are
sized for one TPU v5e chip, as is ``olmoe1b7b-8of16`` (OLMoE-1B-7B-0125
widths, a routed FFN of 64 experts with 8 a token, depth cut to 8 of 16
layers) and ``dsv2-8of60-ep8`` (DeepSeek-V2 widths: latent attention, a
leading dense layer and 7 expert layers of which this chip holds one routing
group, 20 of the router's 160 experts; vocabulary cut to an eighth);
``olmohybrid7b-16of32`` is Olmo-Hybrid-7B's widths with 16 of 32 layers
(four periods of three Gated DeltaNet layers and one full-attention layer:
a recurrent state by slot beside a KV pool of 4 layers);
``olmoe-tiny``, ``dsv2-tiny`` and ``olmohybrid-tiny`` are their CPU-runnable
twins. Weights are random, made from ``--seed``.
Prompts are token-id arrays (the framework ships no tokenizer) — see
README "Serving over HTTP" for curl examples.

The first stdout line is one JSON banner reporting what actually runs:
the device JAX placed the server on, the attention path in effect and
the compile-cache directory, beside the engine's effective settings.
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import threading


PRESETS = ("tiny", "350m", "llama7b-8of32", "olmoe-tiny",
           "olmoe1b7b-8of16", "dsv2-tiny", "dsv2-8of60-ep8",
           "olmohybrid-tiny", "olmohybrid7b-16of32")


def build_model(preset, decode_attention, seed):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         llama_7b, llama_tiny)
    paddle.seed(seed)
    if preset.startswith("dsv2"):
        from paddle_tpu.models.deepseek_v2 import (
            DeepseekV2Config, DeepseekV2ForCausalLM, deepseek_v2_tiny)
        if preset == "dsv2-tiny":
            return DeepseekV2ForCausalLM(deepseek_v2_tiny(
                decode_attention=decode_attention))
        # every width of DeepseekV2Config()'s defaults, the published ones;
        # the cut is benchmark/configs/deepseek-v2-serve-8L-ep8.json's: 8 of
        # 60 layers, routing group 0 of the router's eight held, an eighth
        # of the vocabulary, 8192 tokens a slot (9.6 GiB of bf16 weights)
        return DeepseekV2ForCausalLM(DeepseekV2Config(
            num_hidden_layers=8, n_routed_experts=20, router_experts=160,
            vocab_size=12800, max_position_embeddings=8192,
            dtype="bfloat16", decode_attention=decode_attention))
    if preset.startswith("olmohybrid"):
        from paddle_tpu.models.olmo_hybrid import (
            OlmoHybridConfig, OlmoHybridForCausalLM, olmo_hybrid_tiny)
        if preset == "olmohybrid-tiny":
            return OlmoHybridForCausalLM(olmo_hybrid_tiny(
                decode_attention=decode_attention))
        # every width of OlmoHybridConfig()'s defaults, the published ones;
        # the cut is benchmark/configs/olmo-hybrid-7b-serve-16L.json's: 16
        # of 32 layers (four whole periods of three linear layers and a
        # full one; 7.64 GiB of bf16 weights), 2304 tokens a slot
        return OlmoHybridForCausalLM(OlmoHybridConfig(
            num_hidden_layers=16, layer_types=OlmoHybridConfig().layer_types[
                :16], max_position_embeddings=2304, dtype="bfloat16",
            decode_attention=decode_attention))
    if preset.startswith("olmoe"):
        from paddle_tpu.models.olmoe import (OlmoeConfig, OlmoeForCausalLM,
                                             olmoe_tiny)
        if preset == "olmoe-tiny":
            return OlmoeForCausalLM(olmoe_tiny(
                decode_attention=decode_attention))
        # every width of OlmoeConfig()'s defaults, the published ones
        # (hidden 2048, 16 x 128 heads, 64 experts of 1024, 8 a token,
        # vocab 50304); only the depth is cut, 16 -> 8 layers, so the
        # bf16 weights (6.6 GiB) leave one 16 GB chip room for the cache
        return OlmoeForCausalLM(OlmoeConfig(
            num_hidden_layers=8, dtype="bfloat16",
            decode_attention=decode_attention))
    if preset == "tiny":
        return LlamaForCausalLM(llama_tiny(decode_attention=decode_attention))
    if preset == "llama7b-8of32":
        # every width of llama_7b() (hidden 4096, 32 x 128 heads, ffn
        # 11008, vocab 32000); only the depth is cut, 32 -> 8 layers, so
        # the bf16 weights (3.5 GiB) leave one 16 GB chip room for cache
        return LlamaForCausalLM(llama_7b(
            num_hidden_layers=8, dtype="bfloat16",
            decode_attention=decode_attention))
    if preset == "350m":
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=32000, hidden_size=1024, intermediate_size=2816,
            num_hidden_layers=24, num_attention_heads=16,
            num_key_value_heads=16, max_position_embeddings=2048,
            dtype="bfloat16", decode_attention=decode_attention))
    raise ValueError(f"unknown preset {preset!r}")


def _model_name(preset):
    return (preset if preset.startswith(("olmo", "dsv2"))
            else f"llama-{preset}")


def _runtime_doc(engine):
    """Banner fields for what the process actually runs on: the device as
    JAX reports it, the attention path in effect, the compile cache."""
    from paddle_tpu.core.device import device_summary
    from paddle_tpu.kernels.pallas_flash import _interpret_mode
    from paddle_tpu.utils import compile_cache
    return {"device": device_summary(),
            "decode_attention": engine.config.decode_attention,
            "pallas_interpret": _interpret_mode(),
            "compile_cache": compile_cache.cache_dir()}


def _process_registry(compile_stats):
    """The /metrics registry, seeded with the process-level series the
    engine does not own: compile accounting and per-device memory."""
    import jax

    from paddle_tpu.profiler.metrics import MetricsRegistry
    r = MetricsRegistry()
    r.counter("serving_compile_cache_hits_total",
              "Programs loaded from the persistent compile cache."
              ).set_fn(lambda: compile_stats.cache_hits)
    r.counter("serving_compile_cache_misses_total",
              "Programs compiled and written to the persistent compile "
              "cache.").set_fn(lambda: compile_stats.cache_misses)
    r.counter("serving_compile_seconds_total",
              "Seconds spent in the backend compiler (or loading a "
              "cached program).").set_fn(
        lambda: compile_stats.compile_seconds)
    in_use = r.gauge("serving_device_bytes_in_use",
                     "Device memory in use, per local device.")
    peak = r.gauge("serving_device_peak_bytes_in_use",
                   "Peak device memory in use, per local device.")
    for d in jax.local_devices():
        if d.memory_stats() is None:    # the CPU backend reports none
            continue
        in_use.set_fn(lambda d=d: d.memory_stats()["bytes_in_use"],
                      device=str(d.id))
        peak.set_fn(lambda d=d: d.memory_stats()["peak_bytes_in_use"],
                    device=str(d.id))
    return r


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving.server",
        description="Streaming HTTP serving gateway over the "
                    "continuous-batching engine.")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="0 = ephemeral (printed at startup)")
    ap.add_argument("--preset", choices=PRESETS, default="tiny",
                    help="tiny: CPU-runnable; 350m; llama7b-8of32: "
                         "Llama-2-7B widths with 8 of 32 layers, bf16; "
                         "olmoe-tiny: CPU-runnable routed FFN; "
                         "olmoe1b7b-8of16: OLMoE-1B-7B-0125 widths with 8 "
                         "of 16 layers, bf16; dsv2-tiny / "
                         "dsv2-8of60-ep8: DeepSeek-V2 (latent attention, "
                         "shared + routed experts of which one routing "
                         "group is held), CPU-runnable / published widths "
                         "with 8 of 60 layers, bf16; olmohybrid-tiny / "
                         "olmohybrid7b-16of32: Olmo-Hybrid-7B (Gated "
                         "DeltaNet layers with a recurrent state by slot, "
                         "every fourth layer full attention), CPU-runnable "
                         "/ published widths with 16 of 32 layers, bf16 "
                         "(the last six serve on the default path only: "
                         "other engine switches raise)")
    ap.add_argument("--decode-attention", choices=("pallas", "jnp"),
                    default="pallas",
                    help="the Pallas attention kernels (compiled on a "
                         "TPU, interpreted on the CPU backend), or the "
                         "jnp oracle the tests compare against")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine fleet size (README 'Engine fleet'): "
                         ">1 fronts N shared-nothing engine replicas "
                         "behind one routed gateway — per-replica "
                         "paged pool/prefix trie/supervisor, compiled "
                         "programs shared per pool geometry, "
                         "replica-labeled /metrics, /debug/fleet, "
                         "POST /fleet/drain|rebalance, and failover-"
                         "to-sibling on replica death")
    ap.add_argument("--router",
                    choices=("round-robin", "least-loaded", "affinity",
                             "class-headroom"),
                    default="affinity",
                    help="fleet routing policy (--replicas > 1): "
                         "round-robin, least-loaded (live KV blocks + "
                         "queue depth), affinity (longest cached-"
                         "prefix match within a load band; the "
                         "default), or class-headroom (lowest "
                         "non-displaceable class pressure for the "
                         "request's priority class — pair with "
                         "--classes)")
    ap.add_argument("--affinity-band", type=int, default=16,
                    help="affinity router's load band (KV blocks + "
                         "queued requests): replicas loaded more than "
                         "this past the minimum are skipped no matter "
                         "how warm their trie is")
    ap.add_argument("--num-slots", default="8",
                    help="KV slots per engine; with --replicas > 1 a "
                         "comma list gives each replica its own value "
                         "(e.g. 8,4 — differing pool geometries keep "
                         "isolated jit caches)")
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help=">1 fuses decode ticks (adds streaming latency)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="waiting-room bound before 429s")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic prefix caching: reuse KV blocks of "
                         "shared prompt prefixes across requests")
    ap.add_argument("--prefix-blocks", type=int, default=None,
                    help="prefix-cache pool size in blocks (default: "
                         "num_slots * max_seq_len / block_size)")
    ap.add_argument("--prefix-block-size", type=int, default=32,
                    help="tokens per cached KV block")
    ap.add_argument("--host-tier-bytes", type=int, default=0,
                    help="host-RAM spill tier behind the prefix trie, in "
                         "bytes (0 disables; needs --prefix-cache): "
                         "evicted chains spill d2h and readmit on a hit; "
                         "with --replicas the per-replica tiers form the "
                         "fleet cache plane (/fleet/cacheplane)")
    ap.add_argument("--prefill-chunk", type=int, default=512,
                    help="chunked prefill: max prompt tokens prefilled "
                         "per engine step (bounds TTFT under mixed "
                         "traffic; 0 disables)")
    ap.add_argument("--headroom-mult", type=float, default=2.0,
                    help="adaptive chunk budget: grant ~this many "
                         "decode-steps' worth of measured throughput to "
                         "prefill chunks per step (0 pins the fixed "
                         "prefill-chunk cap). The decode baseline is "
                         "measured on the program that carries chunks: "
                         "the default step runs chunk-free steps at a "
                         "smaller packed size and grants the cap; "
                         "--decode-ticks > 1 and --spec-decode adapt")
    ap.add_argument("--decode-ticks", type=int, default=1,
                    help="multi-tick decode: fuse up to this many "
                         "on-device decode "
                         "ticks behind ONE host sync when every "
                         "running slot is in pure decode — EOS/budget "
                         "cuts are masked on device, streams stay "
                         "byte-identical, and the host round-trip "
                         "amortizes n-fold (tokens stream in bursts "
                         "of up to n). Mixed traffic clamps back to "
                         "single-tick. 1 = off")
    ap.add_argument("--kv-dtype", choices=("pool", "int8", "fp8"),
                    default="pool",
                    help="KV cache storage dtype (README 'Quantized "
                         "serving'): 'pool' stores at the model dtype "
                         "(the default), "
                         "'int8' serves from the block-quantized pool "
                         "(appends "
                         "quantize on write, the attention kernels "
                         "upcast in-register after the table-indirect "
                         "DMA, ~4x pool HBM cut vs fp32 = ~4x "
                         "concurrent slots at a fixed budget), 'fp8' "
                         "stores float8_e4m3fn with per-BLOCK scale "
                         "planes — fewer scale bytes per cached token "
                         "than int8's per-row planes and no quantize "
                         "arithmetic on the append path")
    ap.add_argument("--quantize-weights",
                    action=argparse.BooleanOptionalAction, default=False,
                    help="int8 weight-only decode matmuls: convert the "
                         "decode-path projection weights once at engine "
                         "build (per-channel absmax scales, dequant "
                         "fused into the matmul) — weight HBM traffic "
                         "drops ~4x vs fp32 at a measured-not-assumed "
                         "quality cost")
    ap.add_argument("--quantize-activations",
                    action=argparse.BooleanOptionalAction, default=False,
                    help="int8xint8 decode projections (requires "
                         "--quantize-weights): quantize each projection input "
                         "per-row at runtime and contract int8 against "
                         "the int8 weights with int32 accumulate — the "
                         "per-layer weight dequant disappears from the "
                         "decode step entirely (greedy streams may "
                         "diverge from full precision)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (README 'Tensor-"
                         "parallel serving'): shard every serving "
                         "program over this many devices on a heads-"
                         "sharded mesh with the paged KV pool "
                         "partitioned per shard (must divide the "
                         "model's head counts). On CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "before launch. 1 = single-chip")
    ap.add_argument("--collective-dtype", choices=("fp", "int8"),
                    default="fp",
                    help="wire dtype of the per-layer tensor-parallel "
                         "all-reduce: 'fp' is a plain psum, 'int8' "
                         "runs it EQuARX-style block-quantized (~3.5x "
                         "fewer cross-chip bytes; greedy streams may "
                         "diverge from the fp wire). "
                         "Ignored (no collectives) at --tp 1")
    ap.add_argument("--collective-overlap",
                    action=argparse.BooleanOptionalAction, default=False,
                    help="TP compute/collective overlap (requires "
                         "--tp > 1): the per-layer all-reduce pair "
                         "runs a chunked reduce-scatter/all-gather "
                         "schedule interleaved with the next "
                         "projection's compute — wire format (incl. "
                         "EQuARX int8) and the collective-bytes "
                         "ledger stay exact, streams byte-identical")
    ap.add_argument("--spec-decode", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="speculative multi-token decode: "
                         "a prompt-lookup n-gram drafter proposes up to "
                         "--spec-k tokens per slot, one ragged-span "
                         "verify scores them, rejected KV rolls back by "
                         "block-tail truncation; streams byte-identical "
                         "to speculation off")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per verify span")
    ap.add_argument("--trace", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="record request-lifecycle/step-phase tracing "
                         "from startup into the ring buffer (read it "
                         "back with GET /debug/trace?steps=0); off = "
                         "zero-cost until /debug/trace?steps=N opens a "
                         "capture window")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="trace ring-buffer capacity in events (oldest "
                         "dropped past it)")
    ap.add_argument("--cost", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="device-boundary cost observatory (exact "
                         "dispatch/transfer/compile accounting behind "
                         "GET /debug/profile and the "
                         "serving_dispatches_total metrics); --no-cost "
                         "reduces every cost site to one attribute "
                         "check")
    ap.add_argument("--watchdog-deadline", type=float, default=30.0,
                    help="supervised driver: a step slower than this "
                         "(seconds) is classified hung and the engine is "
                         "rebuilt with in-flight requests recovered by "
                         "recompute (0 disables the watchdog)")
    ap.add_argument("--max-restarts", type=int, default=8,
                    help="engine rebuild budget after fatal/hung step "
                         "faults before the gateway gives up (0 disables "
                         "crash recovery)")
    ap.add_argument("--classes", default=None,
                    help="multi-tenant SLO priority classes (README "
                         "'Multi-tenant SLO serving'): comma list of "
                         "name[*][:reserved_slots], highest priority "
                         "first — e.g. 'latency:1,standard,batch*'. "
                         "'*' marks the default class for unlabeled "
                         "requests (else the last listed). Requests "
                         "pick a tier via the priority_class body "
                         "field or X-Priority-Class header; unknown "
                         "names 400. Default: one neutral class "
                         "(policy off, FIFO baseline)")
    ap.add_argument("--slo-ttft-ms", default=None,
                    help="per-class TTFT SLO targets in ms, aligned "
                         "with --classes (comma list; 0 or a missing "
                         "tail entry = no target). An urgent waiter "
                         "past half its target preempts strictly-"
                         "lower-class running work by recompute")
    ap.add_argument("--slo-tpot-ms", default=None,
                    help="per-class TPOT SLO targets in ms, aligned "
                         "with --classes (comma list; 0 = no target). "
                         "Observed per finished request into "
                         "serving_slo_misses_total{class,slo='tpot'}")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quiet", action="store_true",
                    help="suppress per-request access logs")
    args = ap.parse_args(argv)

    from .httpd import serve, serve_fleet
    try:
        slots = [int(s) for s in str(args.num_slots).split(",")
                 if s.strip()]
    except ValueError:
        ap.error(f"--num-slots must be an int or a comma list of ints, "
                 f"got {args.num_slots!r}")
    if not slots:
        ap.error(f"--num-slots must name at least one value, "
                 f"got {args.num_slots!r}")
    if len(slots) > 1 and args.replicas <= 1:
        ap.error("--num-slots with a comma list needs --replicas > 1 "
                 "(one value per replica)")
    if len(slots) > 1 and len(slots) != args.replicas:
        ap.error(f"--num-slots names {len(slots)} values for "
                 f"--replicas {args.replicas}")
    from paddle_tpu.utils import compile_cache
    registry = _process_registry(compile_cache.enable())
    model = build_model(args.preset, args.decode_attention, args.seed)
    kv_dtype = None if args.kv_dtype == "pool" else args.kv_dtype
    if args.replicas > 1:
        num_slots = slots if len(slots) > 1 else slots[0]
        server = serve_fleet(
            model, replicas=args.replicas, router=args.router,
            affinity_band=args.affinity_band,
            host=args.host, port=args.port, num_slots=num_slots,
            max_seq_len=args.max_seq_len, decode_chunk=args.decode_chunk,
            max_queue=args.max_queue, model_name=_model_name(args.preset),
            registry=registry, prefix_cache=args.prefix_cache,
            prefix_blocks=args.prefix_blocks,
            prefix_block_size=args.prefix_block_size,
            host_tier_bytes=args.host_tier_bytes,
            prefill_chunk=args.prefill_chunk,
            headroom_mult=args.headroom_mult or None,
            spec_decode=args.spec_decode, spec_k=args.spec_k,
            decode_ticks=args.decode_ticks, kv_dtype=kv_dtype,
            quantize_weights=args.quantize_weights,
            quantize_activations=args.quantize_activations,
            tp=args.tp, collective_dtype=args.collective_dtype,
            collective_overlap=args.collective_overlap,
            classes=args.classes, slo_ttft_ms=args.slo_ttft_ms,
            slo_tpot_ms=args.slo_tpot_ms,
            trace=args.trace, trace_buffer=args.trace_buffer,
            cost=args.cost,
            watchdog_deadline_s=args.watchdog_deadline or None,
            max_restarts=args.max_restarts,
            log_fn=None if args.quiet else
            (lambda m: print(m, file=sys.stderr)))
        fleet = server.fleet
        print(json.dumps({
            "listening": server.url, "preset": args.preset,
            **_runtime_doc(fleet.replicas[0].gateway.engine),
            "replicas": len(fleet.replicas),
            "router": fleet.router.name,
            "num_slots": [r.gateway.engine.num_slots
                          for r in fleet.replicas],
            "prefix_cache": bool(args.prefix_cache),
            "prefill_chunk": [r.gateway.engine.prefill_chunk
                              for r in fleet.replicas],
            "spec_decode": fleet.replicas[0].gateway.engine.spec_decode,
            "decode_ticks":
                fleet.replicas[0].gateway.engine.decode_ticks,
            # effective-value idiom: the engines' actual storage dtype
            # and weight mode, not the flag spelling
            "kv_dtype": fleet.replicas[0].gateway.engine.kv_dtype,
            "quantize_weights":
                fleet.replicas[0].gateway.engine.quantize_weights,
            "quantize_activations":
                fleet.replicas[0].gateway.engine.quantize_activations,
            # effective-value idiom: the engines' ACTUAL mesh shape
            # (devices per replica on the "tp" axis) and the wire
            # dtype their per-layer all-reduce really runs
            "tp": fleet.replicas[0].gateway.engine.tp,
            "mesh_shape":
                {"tp": fleet.replicas[0].gateway.engine.tp},
            "collective_dtype":
                fleet.replicas[0].gateway.engine.collective_dtype,
            # effective-value idiom: whether the engines' all-reduce
            # pair really runs the overlap schedule
            "collective_overlap":
                fleet.replicas[0].gateway.engine.collective_overlap,
            # effective-value idiom: the parsed class table the fleet's
            # engines actually schedule with (ranks, ms targets,
            # reserved headroom, the default marker) — not the flag
            # spelling
            "classes": fleet.classes.doc(),
            "trace": fleet.tracer.enabled,
            "cost": fleet.replicas[0].gateway.cost is not None,
            "endpoints": ["/v1/completions", "/healthz", "/metrics",
                          "/debug/trace", "/debug/requests",
                          "/debug/profile", "/debug/fleet",
                          "/fleet/drain", "/fleet/rebalance",
                          "/fleet/cacheplane"]}),
            flush=True)
        stop = threading.Event()
        for sig in (signal.SIGINT, signal.SIGTERM):
            signal.signal(sig, lambda *_: stop.set())
        stop.wait()
        print("# draining fleet...", file=sys.stderr)
        server.shutdown(drain=True, timeout=60)
        print("# stopped", file=sys.stderr)
        return 0
    server = serve(
        model, host=args.host, port=args.port, num_slots=slots[0],
        max_seq_len=args.max_seq_len, decode_chunk=args.decode_chunk,
        max_queue=args.max_queue, model_name=_model_name(args.preset),
        registry=registry,
        prefix_cache=args.prefix_cache, prefix_blocks=args.prefix_blocks,
        prefix_block_size=args.prefix_block_size,
        host_tier_bytes=args.host_tier_bytes,
        prefill_chunk=args.prefill_chunk,
        headroom_mult=args.headroom_mult or None,
        spec_decode=args.spec_decode, spec_k=args.spec_k,
        decode_ticks=args.decode_ticks, kv_dtype=kv_dtype,
        quantize_weights=args.quantize_weights,
        quantize_activations=args.quantize_activations,
        tp=args.tp, collective_dtype=args.collective_dtype,
        collective_overlap=args.collective_overlap,
        classes=args.classes, slo_ttft_ms=args.slo_ttft_ms,
        slo_tpot_ms=args.slo_tpot_ms,
        trace=args.trace, trace_buffer=args.trace_buffer,
        cost=args.cost,
        watchdog_deadline_s=args.watchdog_deadline or None,
        max_restarts=args.max_restarts,
        log_fn=None if args.quiet else
        (lambda m: print(m, file=sys.stderr)))
    print(json.dumps({"listening": server.url, "preset": args.preset,
                      **_runtime_doc(server.gateway.engine),
                      "num_slots": slots[0],
                      "prefix_cache": bool(args.prefix_cache),
                      # report what actually runs: the engine's
                      # block-rounded chunk, 0 when chunking is off
                      "prefill_chunk":
                      server.gateway.engine.prefill_chunk,
                      "spec_decode": server.gateway.engine.spec_decode,
                      "spec_k": server.gateway.engine.spec_k,
                      # report what actually runs: the engine's
                      # effective multi-tick fuse depth (1 = off)
                      "decode_ticks": server.gateway.engine.decode_ticks,
                      # effective-value idiom: the engine's actual KV
                      # storage dtype ("int8" or the pool array dtype)
                      # and whether decode weights really run int8
                      "kv_dtype": server.gateway.engine.kv_dtype,
                      "quantize_weights":
                      server.gateway.engine.quantize_weights,
                      "quantize_activations":
                      server.gateway.engine.quantize_activations,
                      # effective-value idiom: the EFFECTIVE mesh
                      # shape (the "tp" axis the programs actually
                      # shard over; 1 = no mesh) and the wire dtype
                      # of the per-layer all-reduce
                      "tp": server.gateway.engine.tp,
                      "mesh_shape": {"tp": server.gateway.engine.tp},
                      "collective_dtype":
                      server.gateway.engine.collective_dtype,
                      # effective-value idiom: whether the all-reduce
                      # pair really runs the overlap schedule (README
                      # "Collective overlap")
                      "collective_overlap":
                      server.gateway.engine.collective_overlap,
                      # effective-value idiom: the EFFECTIVE class
                      # table the engine schedules with (parsed ranks,
                      # ms targets, reserved headroom, default marker)
                      "classes": server.gateway.engine.classes.doc(),
                      # report what actually runs: whether the tracer
                      # is RECORDING now (the persistent --trace mode)
                      # and the effective ring capacity
                      "trace": server.gateway.tracer.enabled,
                      "trace_buffer": server.gateway.tracer.capacity,
                      # effective-value idiom: whether the cost
                      # observatory is actually accounting
                      "cost": server.gateway.cost is not None,
                      "watchdog_deadline_s":
                      server.gateway.watchdog_deadline_s,
                      "max_restarts": server.gateway.max_restarts,
                      "endpoints": ["/v1/completions", "/healthz",
                                    "/metrics", "/debug/trace",
                                    "/debug/requests",
                                    "/debug/profile"]}), flush=True)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda *_: stop.set())
    stop.wait()
    print("# draining...", file=sys.stderr)
    server.shutdown(drain=True, timeout=60)
    print("# stopped", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
