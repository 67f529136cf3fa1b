"""The second reading for the Jamba cell's limit: the cell's own check,
``kinds/serve_arch._reference_check`` itself, on the system as served and on
deliberately degraded or broken systems, at the published widths on the chip
(``--mid``: a bfloat16 model of hidden 256 on the CPU). Not run by the
benchmark; a builder runs it when the check, the model or the traffic file's
limit change, and writes the readings beside the limit
(``traffic/longdoc-chunked-closed.json``, PERF.md section 6, PR 50):

    chiprun -- python3 benchmark/check_controls_jamba.py 2147000701 \\
        as_served,fp8_weights

One process, a seed after another. Each variant builds an engine with
``serve()``'s defaults, serves ``trafficgen.check_prompts`` of the mix
(sixteen to twenty-four chunks of the unified step, then decode rows) and
hands model, reference and payload to the unedited check. The variants patch
the program from outside:

- ``norms_dropped``: the three RMSNorms inside the Mamba mixer left out (the
  shared mixer as Phi-4-mini-flash runs it);
- ``attn_offset_6``: the attention layers one place early in their period
  (layers 6 and 20): the same weights, another order;
- ``bf16_state``: the store's float32 states rounded to bfloat16 after every
  step (a chunk boundary, a decoded token);
- ``lost_tail``: the convolution's stored inputs zeroed between a prompt's
  chunks (a chunk boundary that forgets);
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
from paddle_tpu.models import jamba
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.utils import compile_cache
import reference_jamba as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/jamba2-3b-serve-28L.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/longdoc-chunked-closed.json")))
check = dict(mix["check"])
if "--mid" in sys.argv:
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=1, head_dim=64, vocab_size=2048,
               num_hidden_layers=8, attn_layer_period=4, attn_layer_offset=2,
               mamba_dt_rank=16, max_position_embeddings=512,
               decode_attention="jnp")
    cfg["engine"] = dict(num_slots=4, max_seq_len=512, prefill_chunk=64)
    check["prompt_tokens"] = {"dist": "uniform", "min": 100, "max": 200}
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
seeds = [int(s) for s in sys.argv[1].split(",")]
names = sys.argv[2].split(",")

real_mixer = decode_mod._mamba_mixer


def low(x, exponent, mantissa):
    return jax.lax.reduce_precision(x, exponent, mantissa)


def norms_dropped(hn, lw, **kw):
    return real_mixer(hn, {k: v for k, v in lw.items()
                           if not k.endswith("_ln")}, **kw)


def one_place_early(params):
    before, after = params["mamba_layers"]
    return dict(params, mamba_layers=(before[:-1], before[-1:] + after))


def round_states(store):
    return (store[0].astype(jnp.bfloat16).astype(jnp.float32), store[1])


def forget_tails(store):
    return (store[0], jnp.zeros_like(store[1]))


VARIANTS = {
    "as_served": {},
    "forward": {"forward": True},
    "norms_dropped": {"mixer": norms_dropped},
    "attn_offset_6": {"tree": one_place_early},
    "bf16_state": {"between": round_states},
    "lost_tail": {"between": forget_tails, "prefilling": True},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = jamba.JambaForCausalLM(jamba.JambaConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


def serve(model, prompts, v):
    eng = ContinuousBatchingEngine(
        model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
    seqs = [eng.submit(GenerationRequest(p, max_new_tokens=check["max_tokens"]))
            for p in prompts]
    between = v.get("between")
    while eng.has_work():
        eng.step()
        if between and (not v.get("prefilling") or any(
                s.status == "prefilling" for s in seqs)):
            eng.cache.store = between(eng.cache.store)
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
KEEP = ("ssm_A_log", "ssm_D", "ssm_dt_b")

for seed in seeds:
    t0 = time.time()
    model = build(seed)
    print("model built", round(time.time() - t0, 1), flush=True)
    prompts = trafficgen.check_prompts(check, seed, cfg["vocab_size"])
    for name, v in VARIANTS.items():
        if name not in names:
            continue
        decode_mod._mamba_mixer = v.get("mixer", real_mixer)
        if v.get("tree"):
            params, tied = type(model).decode_params(model)
            model.decode_params = lambda p=v["tree"](params): (p, tied)
        if v.get("weights"):
            # in place, a matrix at a time: two copies do not fit
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
            served = serve(model, prompts, v)
        t_served = time.time() - t
        gc.collect()
        decode_mod._mamba_mixer = real_mixer
        model.__dict__.pop("decode_params", None)
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
