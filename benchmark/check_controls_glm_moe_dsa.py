"""The second reading for the GLM-5.2 cell's limits: the cell's own check,
``kinds/serve_arch._reference_check`` itself, on the system as served and on
deliberately degraded or broken systems, at the published widths on the chip
(``--mid``: a float32 model of hidden 256 with ``index_topk`` 64 on the CPU).
Not run by the benchmark; a builder runs it when the check, the model or the
traffic file's limits change, and writes the readings beside the limits
(``traffic/sparse-longctx-decode-closed.json``, PERF.md section 6, PR 43):

    chiprun -- python3 benchmark/check_controls_glm_moe_dsa.py 2147000701 as_served,no_selection

One process, a seed after another. Each variant builds an engine with
``serve()``'s defaults, serves ``trafficgen.check_prompts`` of the mix and
hands model, reference and payload to the unedited check. The variants patch
the program from outside:

- the SELECTION: ``no_selection`` (every seen position: dense attention),
  ``first_k`` / ``last_k`` (the first / last ``index_topk`` seen positions),
  ``keys_lost`` (every index key cached by an EARLIER step reads as zero: a
  pool that is not carried from step to step, so only a chunk's own keys
  score), ``shared_selects`` (every layer has an indexer of its own, the
  ``shared`` layers' all zero: they select the first ``index_topk``
  positions instead of borrowing; rebuilds the model);
- the ROUTER: ``softmax_router``, ``bias_not_in_selection``,
  ``bias_in_weights``, ``wrong_first_held`` (pairs for the picks that land
  on the next chip's experts, multiplied by this chip's);
- ``dense_both`` (no fault: the answer to "where does the system's own margin
  come from"): ``index_topk`` over every judged context in program AND
  reference, so both attend densely and no near-tie of the selection can be
  decided two ways; what is left is bfloat16's rounding alone;
- ``fp8_weights``: every weight matrix rounded to float8_e4m3fn for the
  engine, judged by the float32 reference on the unrounded weights (kept
  last: it rebuilds the model).
"""
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
from paddle_tpu.kernels import dsa as dsa_mod
from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models import glm_moe_dsa as glm
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.routing_record import RoutingRecord
from paddle_tpu.utils import compile_cache
import reference_glm_moe_dsa as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/glm-5.2-serve-6L-ep16.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/sparse-longctx-decode-closed.json")))
check = dict(mix["check"])
MID = "--mid" in sys.argv
if MID:
    cfg.update(hidden_size=256, intermediate_size=512,
               moe_intermediate_size=96, num_attention_heads=8,
               num_key_value_heads=8, q_lora_rank=96, kv_lora_rank=64,
               qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
               index_n_heads=4, index_head_dim=32, index_topk=64,
               vocab_size=2048, max_position_embeddings=1024,
               dtype="float32")
    cfg["engine"] = dict(num_slots=4, max_seq_len=1024, prefill_chunk=64,
                         headroom_mult=None)
    check["prompt_tokens"] = {"dist": "uniform", "min": 200, "max": 400}
    cfg["decode_attention"] = "jnp"
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
seeds = [int(s) for s in sys.argv[1].split(",")]
names = sys.argv[2].split(",")
TOPK = cfg["index_topk"]
#: over every judged context: program AND reference attend densely
DENSE_TOPK = check["prompt_tokens"]["max"] + check["max_tokens"] + 2040
NEG = dsa_mod.NEG_INF

real_route, real_ffn, real_select = (moe_mod._route, decode_mod.moe_ffn,
                                     decode_mod.dsa_select)
real_scores = (decode_mod.dsa_index_scores_pallas,
               decode_mod.dsa_index_scores_reference)


def e4m3(v):
    # float8_e4m3fn's grid by arithmetic (check_controls.py)
    x = jnp.clip(v.astype(jnp.float32), -448.0, 448.0)
    e = jnp.maximum(jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 1e-30))), -6.0)
    step = jnp.exp2(e - 3.0)
    return (jnp.round(x / step) * step).astype(v.dtype)


def _seen(scores):
    return scores > 0.5 * NEG


def no_selection(scores, k):
    return _seen(scores)


def first_k(scores, k):
    return _seen(scores) & (jnp.arange(scores.shape[1])[None, :] < k)


def last_k(scores, k):
    seen = _seen(scores)
    n = jnp.sum(seen, -1, keepdims=True)
    return seen & (jnp.arange(scores.shape[1])[None, :] >= n - k)


def keys_lost(fn):
    def scores(q_i, w_i, pool, tables, qstart, qlen, kvlen, **kw):
        out = fn(q_i, w_i, pool, tables, qstart, qlen, kvlen, **kw)
        _, seg, _ = dsa_mod._token_meta(q_i.shape[0], qstart, qlen, kvlen)
        first = jnp.take(kvlen - qlen, seg)     # the span's first position
        earlier = jnp.arange(out.shape[1])[None, :] < first[:, None]
        return jnp.where(earlier & _seen(out), 0.0, out)
    return scores


def _route_with(old, new):
    src = inspect.getsource(real_route)
    assert old in src, old
    ns = dict(moe_mod.__dict__)
    exec(src.replace(old, new), ns)
    return ns["_route"]


def wrong_first_held(h, *w, first_held=0, **kw):
    return real_ffn(h, *w, first_held=first_held + cfg["n_routed_experts"],
                    **kw)


VARIANTS = {
    "as_served": {},
    "no_selection": {"select": no_selection},
    "first_k": {"select": first_k},
    "last_k": {"select": last_k},
    "keys_lost": {"scores": keys_lost},
    "softmax_router": {"route": _route_with(
        "probs = jax.nn.sigmoid(logits)",
        "probs = jax.nn.softmax(logits, axis=-1)")},
    "bias_not_in_selection": {"route": _route_with(
        "probs + router_bias.astype(jnp.float32)", "probs")},
    "bias_in_weights": {"route": _route_with(
        "w = jnp.take_along_axis(probs, idx, axis=-1)",
        "w = jnp.take_along_axis(probs + router_bias.astype(jnp.float32), "
        "idx, axis=-1)")},
    "wrong_first_held": {"ffn": wrong_first_held},
    "shared_selects": {"rebuild": "all_full"},      # rebuilds the model
    "dense_both": {"rebuild": "dense_both"},        # rebuilds the model
    "fp8_weights": {"rebuild": "fp8"},              # last: rebuilds the model
}


def build(seed, **override):
    paddle.seed(seed)
    m = glm.GlmMoeDsaForCausalLM(glm.GlmMoeDsaConfig(
        **{**common.model_keys(cfg), **override}, dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


low = jax.jit(lambda v: jax.lax.map(e4m3, v) if v.ndim >= 3 else e4m3(v),
              donate_argnums=0)


def all_full(kinds, n_dense, held, seed):
    """The model with an indexer in EVERY layer: the true ones' weights
    (``held``, on the host) where the list has ``full``, zeros elsewhere.
    Every other weight is the same draw (a stack's key follows its name, not
    the stacks' sizes)."""
    m = build(seed, indexer_types=["full"] * len(kinds))
    for name in glm._INDEXER:
        val = jnp.zeros_like(getattr(m, name).value)
        slot = 0
        for i, kind in enumerate(kinds[n_dense:]):
            if kind == "full":
                val = val.at[i].set(jnp.asarray(held[name][slot]))
                slot += 1
        setattr(m, name, None)
        setattr(m, name, Parameter(val))
    return m


model = None
for seed in seeds:
    t0 = time.time()
    # one model at a time: the seed before's goes first
    model = None
    gc.collect()
    jax.clear_caches()
    model = build(seed)
    print("model built", round(time.time() - t0, 1), flush=True)
    prompts = trafficgen.check_prompts(check, seed, cfg["vocab_size"])
    for name, v in VARIANTS.items():
        if name not in names:
            continue
        decode_mod.dsa_select = v.get("select", real_select)
        moe_mod._route = v.get("route", real_route)
        decode_mod.moe_ffn = v.get("ffn", real_ffn)
        wrap = v.get("scores", lambda fn: fn)
        decode_mod.dsa_index_scores_pallas = wrap(real_scores[0])
        decode_mod.dsa_index_scores_reference = wrap(real_scores[1])
        record = RoutingRecord()
        if v.get("rebuild") == "fp8":
            # in place, a matrix at a time: two copies do not fit
            for pname in [n for n, _ in model.named_parameters()]:
                val = getattr(model, pname).value
                if val.ndim < 2 or pname == "router_bias":
                    continue
                setattr(model, pname, None)
                setattr(model, pname, Parameter(low(val)))
                del val
        elif v.get("rebuild") == "all_full":
            # one model at a time: two do not fit beside an engine
            kinds = model.config.indexer_types
            n_dense = model.config.first_k_dense_replace
            held = {n: np.asarray(getattr(model, n).value.astype(jnp.float32))
                    for n in glm._INDEXER}
            model = None
            gc.collect()
            jax.clear_caches()
            model = all_full(kinds, n_dense, held, seed)
        elif v.get("rebuild") == "dense_both":
            model = None
            gc.collect()
            jax.clear_caches()
            model = build(seed, index_topk=DENSE_TOPK)
        model.routing_record = record
        jax.clear_caches()
        t = time.time()
        eng = ContinuousBatchingEngine(
            model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
        outs = eng.generate([GenerationRequest(
            p, max_new_tokens=check["max_tokens"]) for p in prompts])
        served = [list(map(int, getattr(o, "tokens", o))) for o in outs]
        t_served = time.time() - t
        del eng
        gc.collect()
        # the model's own forward (positions no program ran) is the sound one
        decode_mod.dsa_select, moe_mod._route = real_select, real_route
        decode_mod.moe_ffn = real_ffn
        (decode_mod.dsa_index_scores_pallas,
         decode_mod.dsa_index_scores_reference) = real_scores
        if v.get("rebuild") in ("fp8", "all_full"):
            model = None
            gc.collect()
            jax.clear_caches()
            model = build(seed)     # the weights as the reference knows them
            model.routing_record = record
        payload = {"prompts": prompts, "served": served,
                   "max_prompt_tokens": check["prompt_tokens"]["max"],
                   "tolerance": check["tolerance"]}
        doc = serve_arch._reference_check(model, reference, payload, check)
        if v.get("rebuild") == "dense_both":
            model = None
            gc.collect()
            jax.clear_caches()
            model = build(seed)
        print(json.dumps({"variant": name, "seed": seed,
                          "lens": [len(p) for p in prompts],
                          "served_s": round(t_served, 1),
                          "seconds": round(time.time() - t, 1), **doc}),
              flush=True)
