"""Benchmark: LLaMA-architecture causal-LM training throughput + MFU on the
local TPU chip(s).

Metric contract (BASELINE.md): MFU = achieved FLOP/s / peak bf16 FLOP/s,
with the FLOP formula stated: 6*N FLOP/token (fwd+bwd, attention term
excluded — same formula as the ≥45% v5p-128 target derivation, so the
number is comparable across chip generations).

The parent imports no JAX: a chip belongs to one process at a time, so each
leg runs in a child that owns the chip and exits before the next starts.
Every child shares one persistent compile cache
(``paddle_tpu.utils.compile_cache``) and names the device it ran on. The
last stdout line is ONE JSON object: {"metric", "value", "unit",
"vs_baseline", "device", "failed"}. A leg that fails is listed under
"failed" and the exit code is 1; nothing is replayed from an earlier run
and nothing falls back to another backend or kernel.

Turning this file into cells is ROADMAP S1.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
METRIC = "llama_350m_train_mfu_bf16"
CONFIG_TIMEOUT_S = 300  # per-config child budget (compile ~30-60s + 13 steps)
SMOKE_TIMEOUT_S = 240   # AOT-compile the Pallas kernels (no execution)
# generate()'s one-shot jit (prefill + scan decode body + Pallas decode
# kernel) compiles slower than a train-step child
DECODE_TIMEOUT_S = 600
# fixed, so the trace of one run is found where the last one was
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "bench_trace")


# Candidate configs, one child subprocess each, best MFU reported. Measured
# rather than assumed: each is timed on-chip and the winner is named in the
# unit string. The levers:
# - no-remat + grad accumulation (`_accum`): fwd+bwd per microbatch inside
#   TrainStep's accum scan keeps only one microbatch's activations live, so
#   full-layer remat (~2N extra FLOP/token, ~14% of a 6N-formula step) is
#   dropped without OOM.
# - head_dim=128 (8 heads x 128 = same H/params as 16 x 64, and the real
#   LLaMA-2 head size): the flash kernel's QK^T/PV contractions fill the
#   128-wide MXU instead of running a 64-deep contraction at ~50%.
# - bhsd head-major layout: projections emit [B,H,S,D]; the flash head fold
#   becomes a free reshape (no HBM transpose pass).
# - fuserope folds rotary into the flash kernels (prologue + dq/dk adjoint —
#   no rotated-q/k HBM round-trip).
# The last two entries are the remat-based configs, which fit where every
# no-remat config runs out of memory.
CONFIGS = [
    ("bhsd+hd128+noremat+accum4+chunk",
     {"attention_layout": "bhsd", "num_attention_heads": 8,
      "num_key_value_heads": 8, "use_recompute": False, "loss_chunk": 512,
      "_accum": 4}),
    ("hd128+noremat+accum4+chunk",
     {"num_attention_heads": 8, "num_key_value_heads": 8,
      "use_recompute": False, "loss_chunk": 512, "_accum": 4}),
    ("bhsd+hd128+noremat+accum4+chunk+fuserope",
     {"attention_layout": "bhsd", "num_attention_heads": 8,
      "num_key_value_heads": 8, "use_recompute": False, "loss_chunk": 512,
      "fuse_rope": True, "_accum": 4}),
    ("noremat+accum4+chunk",
     {"use_recompute": False, "loss_chunk": 512, "_accum": 4}),
    ("bhsd", {"attention_layout": "bhsd"}),
    ("base", {}),
]


def _start_child():
    """Every child that compiles for the chip: place the compile cache and
    name the device, as JAX reports it, for the child's result line."""
    from paddle_tpu.core.device import device_summary
    from paddle_tpu.utils import compile_cache
    compile_cache.enable()
    return device_summary()


def _measure_config(name, overrides, iters=10):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.profiler.metrics import peak_flops_per_chip

    paddle.seed(0)
    # ~350M-param llama sized for a single v5e chip in bf16 + fp32 adam state
    kw = dict(vocab_size=32000, hidden_size=1024, intermediate_size=2816,
              num_hidden_layers=24, num_attention_heads=16,
              num_key_value_heads=16, max_position_embeddings=2048,
              use_recompute=True, dtype="bfloat16")
    kw.update(overrides)
    accum = int(kw.pop("_accum", 1))
    batch = int(kw.pop("_B", 8))
    cfg = LlamaConfig(**kw)
    model = LlamaForCausalLM(cfg)
    n_params = model.num_params()
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    step = TrainStep(model, lambda loss, _lab: loss, opt)

    B, S = batch, 2048
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32))

    def run_step():
        if accum > 1:
            return step.accum_step((ids, ids), (ids,), accum)
        return step.step((ids, ids), (ids,))

    # compile + warmup; float() waits for the device
    t0 = time.perf_counter()
    for _ in range(3):
        float(run_step().value)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = run_step()
    final_loss = float(loss.value)  # forces the whole dependency chain
    dt = time.perf_counter() - t0

    n_chips = jax.device_count()
    tokens_per_sec = iters * B * S / dt
    peak = peak_flops_per_chip() * n_chips
    mfu = tokens_per_sec * 6.0 * n_params / peak
    return {"name": name, "mfu": float(mfu), "tok_s": tokens_per_sec,
            "loss": final_loss, "n_params": n_params, "peak": peak,
            "step_ms": dt / iters * 1000, "warm_s": compile_s}


def main_one_config(idx):
    """Child: measure ONE config, print its result dict as JSON. Each
    config gets its own OS process because a wedged compile / device hang
    blocks in C and no in-process watchdog (signal/alarm) can preempt it —
    only the parent's subprocess timeout bounds it."""
    device = _start_child()
    name, overrides = CONFIGS[idx]
    print(json.dumps({**_measure_config(name, overrides), "device": device}))
    return 0


def _measure_decode(max_new=256, B=8, prompt=128, attn="pallas"):
    """Decode throughput on the 350M config: jitted generate with the
    ragged Pallas decode kernel (kernels/pallas_decode.py), or the jnp
    masked-attention decode path (``--decode jnp``). Timed run is
    the SECOND call (same shapes -> cached executable); prefill is one
    128-token forward vs `max_new` sequential steps, so the figure is
    decode-dominated. Reported via DecodeMeter (2N fwd FLOPs/token; decode
    is weight-streaming-bound so mbu ~ bandwidth utilization)."""
    import numpy as np_

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler.metrics import DecodeMeter

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1024,
                      intermediate_size=2816, num_hidden_layers=24,
                      num_attention_heads=16, num_key_value_heads=16,
                      max_position_embeddings=2048, dtype="bfloat16",
                      decode_attention=attn)
    model = LlamaForCausalLM(cfg)
    rng = np_.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, prompt)).astype(np_.int32))
    print("# decode: model built, compiling generate()", file=sys.stderr)
    sys.stderr.flush()
    out = model.generate(ids, max_new_tokens=max_new, seed=0)  # compile
    _ = out.numpy()
    print("# decode: compile+warm done, timing", file=sys.stderr)
    sys.stderr.flush()
    meter = DecodeMeter(n_params=model.num_params())
    meter.start()
    out = model.generate(ids, max_new_tokens=max_new, seed=0)
    _ = out.numpy()  # the host copy waits for the device
    meter.end_decode(tokens=B * max_new)
    rep = meter.report()
    return {"name": f"decode[{attn}]", "ok": True, "attn": attn,
            "decode_tok_s": float(rep["decode_tokens_per_sec"]),
            "decode_mbu": float(rep.get("decode_mbu", 0.0)),
            "B": B, "prompt": prompt, "max_new": max_new}


def main_trace(idx):
    """Re-run ONE config for a few steps under jax.profiler and print the
    top op-time sinks parsed from the XPlane trace (profiler/xplane.py, no
    TensorFlow dependency)."""
    device = _start_child()
    import jax

    name, overrides = CONFIGS[idx]
    os.makedirs(TRACE_DIR, exist_ok=True)
    # _measure_config warms its own executable for 3 steps before timing,
    # so compile lands at the start of the trace and the timed steps are
    # clean; a separate warm call would just rebuild + recompile
    jax.profiler.start_trace(TRACE_DIR)
    r = _measure_config(name, overrides, iters=4)
    jax.profiler.stop_trace()
    from paddle_tpu.profiler.xplane import op_statistics_with_fallback
    rows, _ = op_statistics_with_fallback(TRACE_DIR, top=12)
    print(json.dumps({"name": name, "mfu": r["mfu"], "device": device,
                      "top_ops": [{"op": x["name"][:80],
                                   "total_ms": round(x["total_ms"], 3),
                                   "count": x["count"]} for x in rows]}))
    return 0


def main_smoke():
    """AOT-lower + compile each Pallas kernel family on the real backend,
    one JSON status line per kernel: the Mosaic-TPU compiler must accept a
    kernel before a config that relies on it can be trusted, and on failure
    the *reason* must be captured, not inferred from a config timeout.

    Compile-only (no execution): `jit(...).lower(shapes).compile()` raises
    on any Mosaic lowering rejection. Exits 1 if any kernel was rejected.
    ``chip_smoke.py`` is the check that also RUNS the serving kernels."""
    device = _start_child()
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas_decode import decode_attention_pallas
    from paddle_tpu.kernels.pallas_flash import flash_attention_bhsd

    bf16 = jnp.bfloat16
    # bench shapes: B=8, H=8, D=128, S=2048 (the hd128 lineage)
    qkv = jax.ShapeDtypeStruct((64, 2048, 128), bf16)
    tab = jax.ShapeDtypeStruct((2048, 128), jnp.float32)

    def train_loss(rope=None, **kw):
        def f(q, k, v, *r):
            o = flash_attention_bhsd(q, k, v, causal=True,
                                     rope=r if rope else None, **kw)
            return jnp.sum(o.astype(jnp.float32))
        return f

    rejected = []

    def compile_one(name, fn, *shapes, grad=True):
        t0 = time.perf_counter()
        try:
            f = jax.grad(fn, argnums=(0, 1, 2)) if grad else fn
            jax.jit(f).lower(*shapes).compile()
            print(json.dumps({"kernel": name, "ok": True, "device": device,
                              "compile_s": round(time.perf_counter() - t0, 1)}))
        except Exception as e:  # report the Mosaic error verbatim, then fail
            rejected.append(name)
            print(json.dumps({"kernel": name, "ok": False, "device": device,
                              "err": f"{type(e).__name__}: {e}"[:400]}))
        sys.stdout.flush()

    compile_one("flash_base", train_loss(), qkv, qkv, qkv)
    compile_one("flash_fuserope", train_loss(rope=True), qkv, qkv, qkv,
                tab, tab)
    compile_one("flash_fb512",
                train_loss(rope=True, block_q=512, block_k=512),
                qkv, qkv, qkv, tab, tab)
    # decode shapes: B=8, H=16, Hkv=16, D=64, S_max=2048 (the --decode run);
    # inference-only kernel, so compile the forward, not a grad
    compile_one("decode_ragged", decode_attention_pallas,
                jax.ShapeDtypeStruct((8, 16, 64), bf16),
                jax.ShapeDtypeStruct((8, 2048, 16, 64), bf16),
                jax.ShapeDtypeStruct((8, 2048, 16, 64), bf16),
                jax.ShapeDtypeStruct((8,), jnp.int32), grad=False)
    return 1 if rejected else 0


def main_7b_layer():
    device = _start_child()
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    from bench_7b_layer import measure as measure_7b
    print(json.dumps({**measure_7b(iters=6), "device": device}))
    return 0


def _run(args, timeout, env=None):
    """Run a python subprocess; return (rc, stdout, stderr) with rc=124 on
    timeout. ``env`` entries override the inherited environment."""
    child_env = None
    if env:
        child_env = dict(os.environ)
        child_env.update(env)
    try:
        p = subprocess.run([sys.executable] + args, timeout=timeout,
                           capture_output=True, text=True, env=child_env,
                           cwd=ROOT)
        return p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        def _text(v):
            if isinstance(v, bytes):
                return v.decode(errors="replace")
            return v or ""
        return 124, _text(e.stdout), _text(e.stderr)


def _parse_result(rc, out):
    """Last {-prefixed stdout line parsed as JSON, or None."""
    line = next((ln for ln in reversed(out.splitlines())
                 if ln.startswith("{")), None)
    if line is None:
        return None
    try:
        return json.loads(line)
    except ValueError:
        return None


def main():
    """The default flow, on the chip: kernel compile smoke, every config,
    the 7B-layer leg, a trace of the best config, decode through the Pallas
    kernel. One child at a time; a leg that fails is reported and makes the
    exit code 1."""
    me = os.path.abspath(__file__)
    failed = []

    def leg(name, args, timeout):
        rc, out, err = _run([me] + args, timeout)
        res = _parse_result(rc, out)
        if rc != 0 or res is None:
            failed.append({"leg": name, "rc": rc,
                           "stderr_tail": err.strip()[-300:]})
            print(f"# leg {name} failed rc={rc}: {err.strip()[-300:]}",
                  file=sys.stderr)
            return None
        return res

    rc, out, err = _run([me, "--smoke"], SMOKE_TIMEOUT_S)
    smoke = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    if rc != 0:
        failed.append({"leg": "smoke", "rc": rc,
                       "rejected": [k for k in smoke if not k.get("ok")],
                       "stderr_tail": err.strip()[-300:]})

    results = [r for r in (leg(f"config:{name}", ["--config", str(i)],
                               CONFIG_TIMEOUT_S)
                           for i, (name, _) in enumerate(CONFIGS)) if r]
    layer7b = leg("layer7b", ["--layer7b"], CONFIG_TIMEOUT_S)
    best = max(results, key=lambda r: r["mfu"]) if results else None
    trace = None
    if best is not None:
        best_idx = next(i for i, (n, _) in enumerate(CONFIGS)
                        if n == best["name"])
        trace = leg("trace", ["--trace", str(best_idx)], CONFIG_TIMEOUT_S)
    decode = leg("decode", ["--decode", "pallas"], DECODE_TIMEOUT_S)

    line = {"metric": METRIC, "value": None, "unit": "MFU (not measured)",
            "vs_baseline": None, "device": None, "failed": failed,
            "pallas_smoke": smoke, "configs": results, "layer7b": layer7b,
            "trace": trace, "decode": decode}
    if best is not None:
        unit = (f"MFU (6N formula, N={best['n_params']/1e6:.0f}M, "
                f"{best['tok_s']:.0f} tok/s/chip, "
                f"peak={best['peak']/1e12:.0f}TF, loss={best['loss']:.3f}, "
                f"cfg={best['name']}")
        if layer7b is not None:
            unit += (f", 7b-layer {layer7b['layer7b_tok_s']} tok/s "
                     f"{layer7b['layer7b_mfu']:.3f} MFU")
        if decode is not None:
            unit += (f", decode[pallas] {decode['decode_tok_s']:.0f} tok/s "
                     f"mbu={decode['decode_mbu']:.2f}")
        line.update(value=round(best["mfu"], 4), unit=unit + ")",
                    vs_baseline=round(best["mfu"] / 0.45, 4),
                    device=best["device"])
    print(json.dumps(line))
    return 1 if failed else 0


if __name__ == "__main__":
    if "--config" in sys.argv:
        sys.exit(main_one_config(int(sys.argv[sys.argv.index("--config") + 1])))
    if "--smoke" in sys.argv:
        sys.exit(main_smoke())
    if "--layer7b" in sys.argv:
        sys.exit(main_7b_layer())
    if "--decode" in sys.argv:
        pos = sys.argv.index("--decode") + 1
        attn = sys.argv[pos] if pos < len(sys.argv) else "pallas"
        device = _start_child()
        print(json.dumps({**_measure_decode(attn=attn), "device": device}))
        sys.exit(0)
    if "--trace" in sys.argv:
        sys.exit(main_trace(int(sys.argv[sys.argv.index("--trace") + 1])))
    sys.exit(main())
