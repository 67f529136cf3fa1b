"""The second reading for the DeepSeek-V2 cell's limits: the cell's own check,
``kinds/serve_arch._reference_check`` itself, on the system as served and on
deliberately degraded or broken systems, at the published widths on the chip
(``--mid``: a bfloat16 model of hidden 512 on the CPU). Not run by the
benchmark; a builder runs it when the check, the model or the traffic file's
limits change, and writes the readings beside the limits
(``traffic/longctx-decode-closed.json``, PERF.md section 6, PR 31):

    chiprun -- python3 benchmark/check_controls.py 2147000701 as_served,fp8_weights

One process a seed (two models do not fit one chip). Each variant builds an
engine with ``serve()``'s defaults, serves ``trafficgen.check_prompts`` of the
mix and hands model, reference and payload to the unedited check. The
variants patch the program from outside: a lower-precision latent or router,
a softmax scale without ``mscale``, faults in the held experts' chain that
leave the router alone, and ``fp8_weights``: every weight matrix rounded to
float8_e4m3fn for the engine and judged by the float32 reference on the
unrounded weights (kept last: it rebuilds the model)."""
import gc
import inspect
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
from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models import deepseek_v2 as dsv2
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.routing_record import RoutingRecord
from paddle_tpu.utils import compile_cache
import reference_deepseek_v2 as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/deepseek-v2-serve-8L-ep8.json")))
mix = json.load(open(os.path.join(ROOT, "benchmark/traffic/longctx-decode-closed.json")))
check = dict(mix["check"])
MID = "--mid" in sys.argv
if MID:
    cfg.update(hidden_size=512, intermediate_size=1024, moe_intermediate_size=192,
               num_attention_heads=8, num_key_value_heads=8, q_lora_rank=192,
               kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
               v_head_dim=32, vocab_size=2048, max_position_embeddings=1024)
    cfg["engine"] = dict(num_slots=4, max_seq_len=1024, prefill_chunk=64,
                         headroom_mult=None)
    check["prompt_tokens"] = {"dist": "uniform", "min": 300, "max": 600}
    cfg["decode_attention"] = "jnp"
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
seeds = [int(s) for s in sys.argv[1].split(",")]      # more than one: --mid
names = sys.argv[2].split(",")

real_rows, real_route, real_mla, real_ffn = (
    decode_mod.latent_rows, moe_mod._route, dsv2.DeepseekV2Config.mla,
    decode_mod.moe_ffn)


def e4m3(v):
    # float8_e4m3fn's grid by arithmetic (3 bits of mantissa, exponents from
    # -6, subnormals below, saturating at 448): XLA drops a convert pair under
    # its default xla_allow_excess_precision
    x = jnp.clip(v.astype(jnp.float32), -448.0, 448.0)
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 1e-30))), -6.0)
    step = jnp.exp2(e - 3.0)
    return (jnp.round(x / step) * step).astype(v.dtype)


def rows_fp8(c_kv, k_pe):
    return real_rows(e4m3(c_kv), e4m3(k_pe))


def rows_int8(c_kv, k_pe):
    def q(x):
        xf = x.astype(jnp.float32)
        s = jnp.max(jnp.abs(xf), -1, keepdims=True) / 127.0
        return (jnp.round(xf / jnp.maximum(s, 1e-30)) * s).astype(x.dtype)
    return real_rows(q(c_kv), q(k_pe))


src = inspect.getsource(real_route).replace(
    "logits = jnp.dot(h2.astype(jnp.float32), router.astype(jnp.float32),\n"
    "                     precision=jax.lax.Precision.HIGHEST)",
    "logits = jnp.dot(h2.astype(jnp.bfloat16), router.astype(jnp.bfloat16))")
assert "bfloat16" in src
ns = dict(moe_mod.__dict__)
exec(src, ns)
route_bf16 = ns["_route"]


def rolled_experts(h, router, w_gate, w_up, w_down, **kw):
    # the held stack one expert out of place (a wrong first_held / order)
    return real_ffn(h, router, jnp.roll(w_gate, 1, 1), jnp.roll(w_up, 1, 1),
                    jnp.roll(w_down, 1, 1), **kw)


def wrong_first_held(h, *w, first_held=0, **kw):
    # pairs made for the picks that land on group 1, multiplied by group 0's
    return real_ffn(h, *w, first_held=first_held + 20, **kw)


def lost_pairs(h, *w, live=None, **kw):
    # every eighth live row loses its pairs (a broken pair list / gmm tile)
    keep = (jnp.arange(live.size) % 8 != 0).reshape(live.shape)
    return real_ffn(h, *w, live=live & keep, **kw)


VARIANTS = {
    "as_served": {},
    "rolled_experts": {"ffn": rolled_experts},     # --mid only: 3 x 2.2 GB
    "wrong_first_held": {"ffn": wrong_first_held},
    "lost_pairs": {"ffn": lost_pairs},
    "fp8_pool": {"rows": rows_fp8},
    "int8_pool": {"rows": rows_int8},
    "bf16_router": {"route": route_bf16},
    "no_mscale": {"mla": property(lambda c: real_mla.fget(c)._replace(
        scale=c.head_dim ** -0.5))},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = dsv2.DeepseekV2ForCausalLM(dsv2.DeepseekV2Config(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


low = jax.jit(lambda v: jax.lax.map(e4m3, v) if v.ndim >= 3 else e4m3(v),
              donate_argnums=0)

for seed in seeds:
    t0 = time.time()
    model = build(seed)
    print("model built", round(time.time() - t0, 1), flush=True)
    prompts = trafficgen.check_prompts(check, seed, cfg["vocab_size"])
    for name, v in VARIANTS.items():
        if name not in names:
            continue
        decode_mod.latent_rows = v.get("rows", real_rows)
        moe_mod._route = v.get("route", real_route)
        decode_mod.moe_ffn = v.get("ffn", real_ffn)
        dsv2.DeepseekV2Config.mla = v.get("mla", real_mla)
        model.routing_record = RoutingRecord()
        if v.get("weights"):
            # in place, a matrix at a time: two copies of 9.6 GiB do not fit
            for pname in [n for n, _ in model.named_parameters()]:
                val = getattr(model, pname).value
                if val.ndim < 2:
                    continue
                setattr(model, pname, None)
                setattr(model, pname, Parameter(low(val)))
                del val
        jax.clear_caches()
        t = time.time()
        eng = ContinuousBatchingEngine(
            model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
        outs = eng.generate([GenerationRequest(p, max_new_tokens=check["max_tokens"])
                             for p in prompts])
        served = [list(map(int, getattr(o, "tokens", o))) for o in outs]
        t_served = time.time() - t
        del eng
        gc.collect()
        # the model's own forward (positions no program ran) is the sound one
        decode_mod.latent_rows, moe_mod._route = real_rows, real_route
        decode_mod.moe_ffn, dsv2.DeepseekV2Config.mla = real_ffn, real_mla
        if v.get("weights"):
            record = model.routing_record
            del model
            gc.collect()
            jax.clear_caches()
            model = build(seed)         # the weights as the reference knows them
            model.routing_record = record
        payload = {"prompts": prompts, "served": served,
                   "max_prompt_tokens": check["prompt_tokens"]["max"],
                   "tolerance": check["tolerance"]}
        doc = serve_arch._reference_check(model, reference, payload, check)
        print(json.dumps({"variant": name, "seed": seed,
                          "lens": [len(p) for p in prompts],
                          "served_s": round(t_served, 1),
                          "seconds": round(time.time() - t, 1), **doc}), flush=True)
