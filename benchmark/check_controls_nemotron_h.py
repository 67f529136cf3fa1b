"""The second reading for the Nemotron-3-Nano cell's limits: the cell's own
check, ``kinds/serve_arch._reference_check`` itself, on the system as served
and on deliberately degraded or broken systems, at the published widths on
the chip (``--mid``: a float32 model of hidden 128 on the CPU). Not run by the
benchmark; a builder runs it when the check, the model or the traffic file's
limits change, and writes the readings beside the limits
(``traffic/hybrid-moe-reasoning-decode-closed.json``, PERF.md section 6, PR
47):

    chiprun -- python3 benchmark/check_controls_nemotron_h.py 2147000701 \\
        as_served,fp8_weights

One process, a seed after another. Each variant builds an engine with
``serve()``'s defaults, serves ``trafficgen.check_prompts`` of the mix (two
to four chunks of the unified step, then decode rows through the decode-only
program) and hands model, reference and payload to the unedited check, the
served routing teacher-forced as the cell does. The variants patch the
program from outside:

- ``forward``: not served at all: the model's own whole-sequence forward in
  the served dtype picks the tokens, which says how far bfloat16 alone is
  from the float32 reference;
- ``state_not_carried`` / ``lost_tail``: the Mamba-2 states / the
  convolution's stored inputs zeroed between a prompt's chunks (a chunk
  boundary that forgets);
- ``state_bf16``: the states rounded to bfloat16 after every step (a store
  held in bfloat16);
- ``skip_dropped``: ``D x`` left out of every Mamba-2 block;
- ``gate_after_norm``: the gated norm in the other order, ``RMSNorm(y) *
  silu(z)``;
- ``relu_not_squared``: ``relu`` where the experts (routed and shared) have
  ``relu^2``;
- ``attention_skipped``: no unit has an attention block (``attn_at`` all -1);
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
from paddle_tpu.kernels import moe_ffn as moe_mod
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.routing_record import RoutingRecord
from paddle_tpu.utils import compile_cache
import reference_nemotron_h as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/nemotron-3-nano-30b-a3b-serve-52L-ep8.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/hybrid-moe-reasoning-decode-closed.json")))
check = dict(mix["check"])
if "--mid" in sys.argv:
    cfg.update(hidden_size=128, num_hidden_layers=12,
               hybrid_override_pattern="MEM*EMEMEM*E", num_attention_heads=8,
               num_key_value_heads=2, head_dim=32, mamba_num_heads=8,
               mamba_head_dim=16, n_groups=2, ssm_state_size=32,
               n_routed_experts=4, router_experts=16, num_experts_per_tok=3,
               moe_intermediate_size=96,
               moe_shared_expert_intermediate_size=192, vocab_size=1024,
               max_position_embeddings=512, dtype="float32",
               decode_attention="jnp")
    cfg["engine"] = dict(num_slots=4, max_seq_len=512, prefill_chunk=64,
                         headroom_mult=None)
    check["prompt_tokens"] = {"dist": "uniform", "min": 100, "max": 200}
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
seeds = [int(s) for s in sys.argv[1].split(",")]
names = sys.argv[2].split(",")

real = dict(mixer=decode_mod._ssd_mixer, norm=decode_mod._gated_group_norm,
            relu2=moe_mod.relu2)


def skip_dropped(hn, lw, **kw):
    return real["mixer"](hn, dict(lw, ssd_D=jnp.zeros_like(lw["ssd_D"])),
                         **kw)


def gate_after_norm(y, z, w, groups, eps):
    f32 = jnp.float32
    return real["norm"](y, jnp.full(z.shape, 1.2784645, f32), w, groups,
                        eps) * jax.nn.silu(z.astype(f32))   # silu(1.278..) = 1


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
    "skip_dropped": {"mixer": skip_dropped},
    "gate_after_norm": {"norm": gate_after_norm},
    "relu_not_squared": {"relu2": lambda x: jnp.maximum(x, 0)},
    "attention_skipped": {"no_attention": True},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = nh.NemotronHForCausalLM(nh.NemotronHConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


def serve(model, prompts, between):
    eng = ContinuousBatchingEngine(
        model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
    seqs = [eng.submit(GenerationRequest(p, max_new_tokens=check["max_tokens"]))
            for p in prompts]
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
KEEP = ("ssd_A_log", "ssd_D", "ssd_dt_b", "router_bias")

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
        decode_mod._ssd_mixer = v.get("mixer", real["mixer"])
        decode_mod._gated_group_norm = v.get("norm", real["norm"])
        decode_mod.relu2 = moe_mod.relu2 = v.get("relu2", real["relu2"])
        record = RoutingRecord()
        model.routing_record = record
        if v.get("no_attention"):
            params, tied = type(model).decode_params(model)
            model.decode_params = lambda p=params, t=tied: (
                dict(p, attn_at=jnp.full_like(p["attn_at"], -1)), t)
        if v.get("weights"):
            # in place, a matrix at a time: two copies of 9.8 GiB do not fit
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
        # the model's own forward (positions no program ran) is the sound one
        decode_mod._ssd_mixer = real["mixer"]
        decode_mod._gated_group_norm = real["norm"]
        decode_mod.relu2 = moe_mod.relu2 = real["relu2"]
        model.__dict__.pop("decode_params", None)
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
                          "lens": [len(p) for p in prompts],
                          "served_s": round(t_served, 1),
                          "seconds": round(time.time() - t, 1), **doc}),
              flush=True)
