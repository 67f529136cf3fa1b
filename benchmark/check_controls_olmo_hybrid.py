"""The second reading for the Olmo-Hybrid cell's limit: the cell's own check,
``kinds/serve_arch._reference_check`` itself, on the system as served and on
deliberately degraded or broken systems, at the published widths on the chip
(``--mid``: a bfloat16 model of hidden 256 on the CPU). Not run by the
benchmark; a builder runs it when the check, the model or the traffic file's
limit change, and writes the readings beside the limit
(``traffic/longout-decode-closed.json``, PERF.md section 6, PR 33):

    chiprun -- python3 benchmark/check_controls_olmo_hybrid.py 2147000701 \\
        as_served,fp8_weights

One process a seed. Each variant builds an engine with ``serve()``'s defaults,
serves ``trafficgen.check_prompts`` of the mix (two chunks of the unified
step, then decode rows) and hands model, reference and payload to the unedited
check. The variants patch the program from outside:

- ``bf16_state``: the recurrent state rounded to bfloat16 after every kernel
  call (``reduce_precision``, which XLA does not drop as it drops a convert
  pair);
- ``no_beta_2``: ``beta`` without the doubling of ``linear_allow_neg_eigval``;
- ``no_decay``: ``g`` = 0, the state never forgets;
- ``lost_tail``: the convolution's stored inputs zeroed between a prompt's
  chunks (a chunk boundary that forgets the last three tokens);
- ``gdn_oracle``: the token-by-token recurrence in place of both kernels
  (attention still through its kernel): a kernel fault shows as a gap between
  this and ``as_served``;
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
from paddle_tpu.models import olmo_hybrid as oh
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.utils import compile_cache
import reference_olmo_hybrid as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/olmo-hybrid-7b-serve-16L.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/longout-decode-closed.json")))
check = dict(mix["check"])
if "--mid" in sys.argv:
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=4, linear_num_key_heads=4,
               linear_num_value_heads=4, linear_key_head_dim=32,
               linear_value_head_dim=64, vocab_size=2048,
               num_hidden_layers=8, layer_types=cfg["layer_types"][:8],
               max_position_embeddings=512, decode_attention="jnp")
    cfg["engine"] = dict(num_slots=4, max_seq_len=512, prefill_chunk=64)
    check["prompt_tokens"] = {"dist": "uniform", "min": 100, "max": 200}
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
seeds = [int(s) for s in sys.argv[1].split(",")]
names = sys.argv[2].split(",")

real = dict(gates=decode_mod.gdn_gates, update=decode_mod.gdn_recurrent_update,
            scan=decode_mod.gdn_chunk_scan, gdn=oh.OlmoHybridConfig.gdn)


def low(x, exponent, mantissa):
    return jax.lax.reduce_precision(x, exponent, mantissa)


def rounded_state(kernel):
    def call(*a, **kw):
        o, st = kernel(*a, **kw)
        return o, low(st, 8, 7)
    return call


def no_beta_2(ab, a_log, dt_bias, neg_eigval):
    return real["gates"](ab, a_log, dt_bias, False)


def no_decay(ab, a_log, dt_bias, neg_eigval):
    g, beta = real["gates"](ab, a_log, dt_bias, neg_eigval)
    return jnp.zeros_like(g), beta


VARIANTS = {
    "as_served": {},
    "forward": {"forward": True},
    "gdn_oracle": {"gdn": property(
        lambda c: real["gdn"].fget(c)._replace(kernel="jnp"))},
    "bf16_state": {"update": rounded_state(real["update"]),
                   "scan": rounded_state(real["scan"])},
    "no_beta_2": {"gates": no_beta_2},
    "no_decay": {"gates": no_decay},
    "lost_tail": {"lost_tail": True},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = oh.OlmoHybridForCausalLM(oh.OlmoHybridConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


def serve(model, prompts, lost_tail):
    eng = ContinuousBatchingEngine(
        model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
    seqs = [eng.submit(GenerationRequest(p, max_new_tokens=check["max_tokens"]))
            for p in prompts]
    while eng.has_work():
        eng.step()
        if lost_tail and any(s.status == "prefilling" for s in seqs):
            states, tails = eng.cache.state
            eng.cache.state = (states, jnp.zeros_like(tails))
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

for seed in seeds:
    t0 = time.time()
    model = build(seed)
    print("model built", round(time.time() - t0, 1), flush=True)
    prompts = trafficgen.check_prompts(check, seed, cfg["vocab_size"])
    for name, v in VARIANTS.items():
        if name not in names:
            continue
        decode_mod.gdn_gates = v.get("gates", real["gates"])
        decode_mod.gdn_recurrent_update = v.get("update", real["update"])
        decode_mod.gdn_chunk_scan = v.get("scan", real["scan"])
        oh.OlmoHybridConfig.gdn = v.get("gdn", real["gdn"])
        if v.get("weights"):
            # in place, a matrix at a time: two copies of 7.6 GiB do not fit
            for pname in [n for n, _ in model.named_parameters()]:
                val = getattr(model, pname).value
                if val.ndim < 2 or "gdn_A_log" in pname or "dt_bias" in pname:
                    continue
                setattr(model, pname, None)
                setattr(model, pname, Parameter(fp8(val)))
                del val
        jax.clear_caches()
        t = time.time()
        if v.get("forward"):
            served = forward_picks(model, prompts)
        else:
            served = serve(model, prompts, v.get("lost_tail"))
        t_served = time.time() - t
        gc.collect()
        decode_mod.gdn_gates = real["gates"]
        decode_mod.gdn_recurrent_update = real["update"]
        decode_mod.gdn_chunk_scan = real["scan"]
        oh.OlmoHybridConfig.gdn = real["gdn"]
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
