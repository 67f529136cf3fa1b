"""The second reading for the Qwen3-Next cell's limits: the cell's own check,
``kinds/serve_arch._reference_check`` itself, on the system as served and on
deliberately degraded or broken systems, at the published widths on the chip
(``--mid``: a float32 model of hidden 128 on the CPU). Not run by the
benchmark; a builder runs it when the check, the model or the traffic file's
limits change, and writes the readings beside the limits
(``traffic/wide-decode-closed.json``, PERF.md section 6, PR 54):

    chiprun -- python3 benchmark/check_controls_qwen3_next.py 2147000701 \\
        as_served,fp8_weights

One process, a seed after another. Each variant builds an engine with
``serve()``'s defaults (``--slots N``: at another number of slots than the
configuration's, which changes a program's shapes and none of its
mathematics), serves ``trafficgen.check_prompts`` of the mix (two chunks of
the unified step, then decode rows through the decode-only program) and hands
model, reference and payload to the unedited check, the served routing
teacher-forced as the cell does. The variants change the program from outside
(a tree with an entry wrong, a static number wrong, a function patched):

- ``forward``: not served at all: the model's own whole-sequence forward in
  the served dtype picks the tokens, which says how far bfloat16 alone is
  from the float32 reference;
- ``state_not_carried`` / ``lost_tail``: the delta rule's states / the
  convolution's stored inputs zeroed between a prompt's chunks (a chunk
  boundary that forgets);
- ``state_bf16``: the states rounded to bfloat16 after every step (a store
  held in bfloat16);
- ``w_for_1_plus_w``: every zero-centred norm weight read as the weight;
- ``attention_gate_dropped``: no ``sigmoid(gate)`` on the heads' output;
- ``whole_head_rotated``: all 256 values of a head rotated, not the first 64;
- ``whole_projection_norm``: q and k normalised over all heads at once;
- ``value_head_on_key_head_h_mod_16``: value head ``h`` on key head ``h % 16``;
- ``beta_doubled``: ``2 sigmoid(b)`` (Olmo-Hybrid's negative eigenvalues);
- ``shared_gate_dropped``: the shared expert without ``sigmoid(x w_sg)``;
- ``weights_not_renormalised``: the picked probabilities as they are;
- ``fp8_weights``: every weight matrix rounded to float8_e4m3fn's precision
  for the engine and judged by the float32 reference on the unrounded weights
  (kept last: it rebuilds the model)."""
import dataclasses
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
from paddle_tpu.models import qwen3_next as qn
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.routing_record import RoutingRecord
from paddle_tpu.utils import compile_cache
import reference_qwen3_next as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/qwen3-next-80b-a3b-serve-12L-ep8.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/wide-decode-closed.json")))
check = dict(mix["check"])
argv = sys.argv[1:]
if "--mid" in argv:
    argv.remove("--mid")
    cfg.update(hidden_size=128, num_hidden_layers=8, num_attention_heads=8,
               num_key_value_heads=2, head_dim=32, linear_num_key_heads=4,
               linear_num_value_heads=8, linear_key_head_dim=16,
               linear_value_head_dim=32, num_experts=4, router_experts=16,
               num_experts_per_tok=3, moe_intermediate_size=96,
               shared_expert_intermediate_size=96, vocab_size=1024,
               max_position_embeddings=512, dtype="float32",
               decode_attention="jnp")
    cfg["engine"] = dict(num_slots=4, max_seq_len=512, prefill_chunk=64,
                         headroom_mult=None)
    check["prompt_tokens"] = {"dist": "uniform", "min": 100, "max": 200}
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
if "--slots" in argv:
    at = argv.index("--slots")
    cfg["engine"]["num_slots"] = int(argv[at + 1])
    del argv[at:at + 2]
seeds = [int(s) for s in argv[0].split(",")]
names = argv[1].split(",")

real = dict(split=decode_mod.gdn_split, gates=decode_mod.gdn_gates)


def tiled_key_heads(u, gdn):
    """Value head ``h`` on key head ``h % key heads``: q and k handed on at
    the value heads' count, tiled."""
    q, k, v = real["split"](u, gdn)
    rep = gdn.heads // (gdn.key_heads or gdn.heads)
    tile = (1,) * (q.ndim - 2) + (rep, 1)
    return jnp.tile(q, tile), jnp.tile(k, tile), v


def doubled_beta(ab, a_log, dt_bias, _neg_eigval):
    return real["gates"](ab, a_log, dt_bias, True)


def every_tree(params, edit):
    out = edit(dict(params))
    out["linear_layers"] = tuple(edit(dict(t))
                                 for t in params["linear_layers"])
    return out


def without(*dropped):
    return lambda tree: {k: v for k, v in tree.items() if k not in dropped}


def ungated_query(tree):
    if "wq" in tree:
        P, H, wide = tree["wq"].shape
        hd = tree["q_norm"].shape[-1]
        tree["wq"] = tree["wq"].reshape(P, H, -1, 2 * hd)[..., :hd].reshape(
            P, H, wide // 2)
    return tree


def whole_projection_norm(tree):
    if "wq" in tree:
        hd = tree["q_norm"].shape[-1]
        tree["q_norm"] = jnp.tile(tree["q_norm"],
                                  (1, tree["wo"].shape[1] // hd))
        tree["k_norm"] = jnp.tile(tree["k_norm"],
                                  (1, tree["wk"].shape[-1] // hd))
    return tree


def zero_store(which):
    def between(eng, seqs):
        if any(s.status == "prefilling" for s in seqs):
            store = list(eng.cache.store)
            store[which] = jnp.zeros_like(store[which])
            eng.cache.store = tuple(store)
    return between


def round_state(eng, _seqs):
    st, tails = eng.cache.store
    eng.cache.store = (st.astype(jnp.bfloat16).astype(st.dtype), tails)


VARIANTS = {
    "as_served": {},
    "forward": {"forward": True},
    "state_not_carried": {"between": zero_store(0)},
    "lost_tail": {"between": zero_store(1)},
    "state_bf16": {"between": round_state},
    "w_for_1_plus_w": {"tree": without("norm_plus_one")},
    "attention_gate_dropped": {
        "tree": lambda p: every_tree(p, ungated_query)},
    "whole_head_rotated": {"config": {"partial_rotary_factor": 1.0}},
    "whole_projection_norm": {
        "tree": lambda p: every_tree(p, whole_projection_norm)},
    "value_head_on_key_head_h_mod_16": {"split": tiled_key_heads},
    "beta_doubled": {"gates": doubled_beta},
    "shared_gate_dropped": {
        "tree": lambda p: every_tree(p, without("ws_sgate"))},
    "weights_not_renormalised": {"config": {"norm_topk_prob": False}},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = qn.Qwen3NextForCausalLM(qn.Qwen3NextConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


def serve(model, prompts, between):
    eng = ContinuousBatchingEngine(
        model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
    seqs = [eng.submit(GenerationRequest(
        p, max_new_tokens=check["max_tokens"])) for p in prompts]
    while eng.has_work():
        eng.step()
        if between is not None:
            between(eng, seqs)
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


def e4m3(x):
    return jax.lax.reduce_precision(x, 4, 3)


fp8 = jax.jit(lambda v: jax.lax.map(e4m3, v) if v.ndim >= 3 else e4m3(v),
              donate_argnums=0)
KEEP = ("gdn_A_log", "gdn_dt_bias")


def restore(model, config):
    """The program as it is: the check's own forward (positions no program
    ran) is the sound one."""
    decode_mod.gdn_split, decode_mod.gdn_gates = real["split"], real["gates"]
    model.__dict__.pop("decode_params", None)
    model.config = config


model = None
for seed in seeds:
    t0 = time.time()
    # one model at a time: the seed before's goes first
    model = None
    gc.collect()
    jax.clear_caches()
    model = build(seed)
    config = model.config
    print("model built", round(time.time() - t0, 1), flush=True)
    prompts = trafficgen.check_prompts(check, seed, cfg["vocab_size"])
    for name, v in VARIANTS.items():
        if name not in names:
            continue
        decode_mod.gdn_split = v.get("split", real["split"])
        decode_mod.gdn_gates = v.get("gates", real["gates"])
        record = RoutingRecord()
        model.routing_record = record
        if "tree" in v:
            params, tied = type(model).decode_params(model)
            wrong = v["tree"](params)
            model.decode_params = lambda p=wrong, t=tied: (p, t)
        if "config" in v:
            model.config = dataclasses.replace(config, **v["config"])
        if v.get("weights"):
            # in place, a matrix at a time: two copies of the weights and the
            # engine's caches do not fit
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
            served = serve(model, prompts, v.get("between"))
        t_served = time.time() - t
        gc.collect()
        restore(model, config)
        jax.clear_caches()
        if v.get("weights"):
            model = None
            gc.collect()
            model = build(seed)     # the weights as the reference knows them
            model.routing_record = record
        payload = {"prompts": prompts, "served": served,
                   "max_prompt_tokens": check["prompt_tokens"]["max"],
                   "tolerance": check["tolerance"]}
        doc = serve_arch._reference_check(model, reference, payload, check)
        print(json.dumps({"variant": name, "seed": seed,
                          "slots": cfg["engine"]["num_slots"],
                          "lens": [len(p) for p in prompts],
                          "served_s": round(t_served, 1),
                          "seconds": round(time.time() - t, 1), **doc}),
              flush=True)
