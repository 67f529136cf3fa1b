"""The second reading for the Phi-4-mini-flash cell's limit: the cell's own
check, ``kinds/serve_arch._reference_check`` itself, on the system as served
and on deliberately degraded or broken systems, at the published widths on
the chip (``--mid``: a bfloat16 model of hidden 256 on the CPU). Not run by
the benchmark; a builder runs it when the check, the model or the traffic
file's limit change, and writes the readings beside the limit
(``traffic/reasoning-decode-closed.json``, PERF.md section 6, PR 37):

    chiprun -- python3 benchmark/check_controls_phi4_flash.py 2147000701 \\
        as_served,fp8_weights

One process, a seed after another. Each variant builds an engine with
``serve()``'s defaults, serves ``trafficgen.check_prompts`` of the mix (two
to four chunks of the unified step, then decode rows) and hands model,
reference and payload to the unedited check. The variants patch the program
from outside:

- ``window_dropped``: every window layer attends from position 0 (a window
  as long as the context; the engine then holds the whole context in its
  rings, so this one runs at 4 slots);
- ``lambda_zero``: ``o2`` is never subtracted (``lambda`` = 0; ``lambda_init``
  keeps its other places);
- ``memory_after_gate``: the middle Mamba layer hands on ``y * silu(z)``, not
  ``y``;
- ``lost_tail`` / ``lost_window``: the convolution's stored inputs / the
  window layers' stored keys zeroed between a prompt's chunks (a chunk
  boundary that forgets);
- ``cross_unwritten``: the middle layer's keys and values never reach the
  pool: the cross layers read rows nobody wrote (zeros);
- ``forward``: not served at all: the model's own whole-sequence forward in
  the served dtype picks the tokens, which says how far bfloat16 alone is from
  the float32 reference;
- ``fp8_weights``: every weight matrix rounded to float8_e4m3fn's precision
  for the engine and judged by the float32 reference on the unrounded weights
  (kept last: it rebuilds the model)."""
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as paddle
from kinds import common, serve_arch
from paddle_tpu.models import phi4_flash as pf
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.utils import compile_cache
import reference_phi4_flash as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/phi-4-mini-flash-serve-32L.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/reasoning-decode-closed.json")))
check = dict(mix["check"])
if "--mid" in sys.argv:
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=8,
               num_key_value_heads=4, vocab_size=2048, num_hidden_layers=8,
               sliding_window=48, max_position_embeddings=512,
               decode_attention="jnp")
    cfg["engine"] = dict(num_slots=4, max_seq_len=512, prefill_chunk=64)
    check["prompt_tokens"] = {"dist": "uniform", "min": 100, "max": 200}
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
seeds = [int(s) for s in sys.argv[1].split(",")]
names = sys.argv[2].split(",")

real = dict(combine=decode_mod._diff_combine, mixer=decode_mod._mamba_mixer,
            write=decode_mod._kv_write)


def low(x, exponent, mantissa):
    return jax.lax.reduce_precision(x, exponent, mantissa)


def lambda_zero(o, lw, eps, dtype):
    o = o.reshape(o.shape[:-2] + (-1, 2, o.shape[-1]))
    o = o.at[..., 1, :].set(0.0)
    return real["combine"](o.reshape(o.shape[:-3] + (-1, o.shape[-1])), lw,
                           eps, dtype)


def memory_after_gate(hn, lw, *, conv, scan):
    out, (tail, st, y) = real["mixer"](hn, lw, conv=conv, scan=scan)
    z = jnp.einsum("bsh,hc->bsc", hn, lw["ssm_in"])[..., y.shape[-1]:]
    return out, (tail, st, y * jax.nn.silu(z.astype(jnp.float32)))


VARIANTS = {
    "as_served": {},
    "forward": {"forward": True},
    "window_dropped": {"window": True},
    "lambda_zero": {"combine": lambda_zero},
    "memory_after_gate": {"mixer": memory_after_gate},
    "lost_tail": {"lost": 1},
    "lost_window": {"lost": 2},
    "cross_unwritten": {"write": lambda pool, at, x: real["write"](
        pool, at, jnp.zeros_like(x))},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = pf.Phi4FlashForCausalLM(pf.Phi4FlashConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


def serve(model, prompts, lost, geometry):
    eng = ContinuousBatchingEngine(
        model, jit_cache={}, **common.serve_engine_kwargs(geometry))
    seqs = [eng.submit(GenerationRequest(p, max_new_tokens=check["max_tokens"]))
            for p in prompts]
    while eng.has_work():
        eng.step()
        if lost and any(s.status == "prefilling" for s in seqs):
            store = list(eng.cache.store)
            store[lost] = jnp.zeros_like(store[lost])
            eng.cache.store = tuple(store)
    return [list(map(int, s.tokens)) for s in seqs]


def forward_picks(model, prompts):
    """The model's own forward (served dtype, whole sequence, no cache),
    greedy, a token at a time on its own picks; every call at one width (the
    rows behind the last token are padding a causal model never sees)."""
    width = check["prompt_tokens"]["max"] + check["max_tokens"]
    out = []
    for p in prompts:
        ids = list(p)
        for _ in range(check["max_tokens"]):
            row = np.zeros((1, width), np.int32)
            row[0, :len(ids)] = ids
            logits = model.forward(row).value
            ids.append(int(jnp.argmax(logits[0, len(ids) - 1])))
        out.append(ids[len(p):])
    return out


fp8 = jax.jit(lambda v: jax.lax.map(lambda x: low(x, 4, 3), v)
              if v.ndim >= 3 else low(v, 4, 3), donate_argnums=0)
KEEP = ("ssm_A_log", "ssm_D", "ssm_dt_b", "lam", "lambda_init")

for seed in seeds:
    t0 = time.time()
    model = build(seed)
    print("model built", round(time.time() - t0, 1), flush=True)
    prompts = trafficgen.check_prompts(check, seed, cfg["vocab_size"])
    for name, v in VARIANTS.items():
        if name not in names:
            continue
        decode_mod._diff_combine = v.get("combine", real["combine"])
        decode_mod._mamba_mixer = v.get("mixer", real["mixer"])
        decode_mod._kv_write = v.get("write", real["write"])
        geometry = dict(cfg["engine"])
        window = model.config.sliding_window
        if v.get("window"):
            model.config.sliding_window = geometry["max_seq_len"]
            geometry["num_slots"] = len(prompts)
        if v.get("weights"):
            # in place, a matrix at a time: two copies of 7.2 GiB do not fit
            for pname in [n for n, _ in model.named_parameters()]:
                val = getattr(model, pname).value
                if val.ndim < 2 or pname.endswith(KEEP):
                    continue
                setattr(model, pname, None)
                setattr(model, pname, Parameter(fp8(val)))
                del val
        jax.clear_caches()
        t = time.time()
        if v.get("forward"):
            served = forward_picks(model, prompts)
        else:
            served = serve(model, prompts, v.get("lost"), geometry)
        t_served = time.time() - t
        gc.collect()
        decode_mod._diff_combine = real["combine"]
        decode_mod._mamba_mixer = real["mixer"]
        decode_mod._kv_write = real["write"]
        model.config.sliding_window = window
        if v.get("weights"):
            del model
            gc.collect()
            jax.clear_caches()
            model = build(seed)     # the weights as the reference knows them
        payload = {"prompts": prompts, "served": served,
                   "max_prompt_tokens": check["prompt_tokens"]["max"],
                   "tolerance": check["tolerance"]}
        doc = serve_arch._reference_check(model, reference, payload, check)
        print(json.dumps({"variant": name, "seed": seed,
                          "lens": [len(p) for p in prompts],
                          "served_s": round(t_served, 1),
                          "seconds": round(time.time() - t, 1), **doc}),
              flush=True)
    del model
    gc.collect()
