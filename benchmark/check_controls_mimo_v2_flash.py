"""The second reading for the MiMo-V2-Flash cell's limits: the cell's own check,
``kinds/serve_arch._reference_check`` itself, on the system as served and on
deliberately degraded or broken systems, at the published widths on the chip
(``--mid``: a float32 model of hidden 128 on the CPU). Not run by the
benchmark; a builder runs it when the check, the model or the traffic file's
limits change, and writes the readings beside the limits
(``traffic/mixedlen-decode-closed.json``, PERF.md section 6, PR 56):

    chiprun -- python3 benchmark/check_controls_mimo_v2_flash.py 2147000701 \\
        as_served,fp8_weights

One process, a seed after another. Each variant builds an engine with
``serve()``'s defaults (``--slots N``: at another number of slots than the
configuration's, which changes a program's shapes and none of its
mathematics), serves ``trafficgen.check_prompts`` of the mix (8 to 24 chunks of
the unified step: a ring wraps six to eighteen times; then decode rows through
the decode-only program) and hands model, reference and payload to the
unedited check, the served routing teacher-forced as the cell does. The
variants change the program from outside (a tree with an entry wrong, a
configuration number wrong, a function patched):

- ``forward``: not served at all: the model's own whole-sequence forward in
  the served dtype picks the tokens, which says how far bfloat16 alone is
  from the float32 reference;
- ``sink_dropped``: the window layers' softmax without its sink column;
- ``sink_on_full_layers``: a sink of 4 on the full layers' softmax too;
- ``window_127`` / ``window_129``: the window one key short / one key long;
- ``thetas_swapped``: the full layers rotated over 10,000 and the window
  layers over 5,000,000;
- ``whole_head_rotated``: all 192 values of a head rotated, not the first 64;
- ``value_scale_dropped``: ``v`` as projected, not times 0.707;
- ``ring_one_block_short``: rings of 20 blocks where the window, a chunk and
  a block need 21 (a chunk's last rows overwrite keys its first rows see);
- ``ring_row_at_pos_mod_128``: a ring's row written at ``pos % 128`` (a ring
  as long as the window, with no room for the step's own span);
- ``bias_in_weights``: the picked experts' weights from ``s + c``;
- ``weights_not_renormalised``: the picked scores as they are;
- ``dense_layer_at_experts_width``: layer 0's SwiGLU cut to 2,048 units;
- ``fp8_weights``: every weight matrix rounded to float8_e4m3fn's precision
  for the engine and judged by the float32 reference on the unrounded weights
  (kept last: it rebuilds the model).

Not here, because the stores' shapes would no longer take the rows (a crash,
not a reading): ``W_o`` fed a head's 192 and 4 KV heads in a window layer;
``tests/test_mimo_v2_flash_serving.py::test_wrong_forward_fails`` pins both on
the CPU."""
import dataclasses
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
from paddle_tpu.models import mimo_v2_flash as mimo
from paddle_tpu.nn.layer import Parameter
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.routing_record import RoutingRecord
from paddle_tpu.utils import compile_cache
import reference_mimo_v2_flash as reference
import trafficgen

compile_cache.enable()
cfg = json.load(open(os.path.join(
    ROOT, "benchmark/configs/mimo-v2-flash-serve-7L-ep16.json")))
mix = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/mixedlen-decode-closed.json")))
check = dict(mix["check"])
argv = sys.argv[1:]
if "--mid" in argv:
    argv.remove("--mid")
    cfg.update(hidden_size=128, intermediate_size=256, num_attention_heads=8,
               num_key_value_heads=2, head_dim=48, v_head_dim=32,
               swa_num_attention_heads=8, swa_num_key_value_heads=4,
               swa_head_dim=48, swa_v_head_dim=32, sliding_window=32,
               n_routed_experts=4, router_experts=16, num_experts_per_tok=3,
               moe_intermediate_size=96, vocab_size=1024,
               max_position_embeddings=512, dtype="float32",
               decode_attention="jnp")
    cfg["engine"] = dict(num_slots=4, max_seq_len=512, prefill_chunk=64,
                         headroom_mult=None)
    check["prompt_tokens"] = {"dist": "uniform", "min": 200, "max": 400}
    cfg["model_keys"] = cfg["model_keys"] + ["decode_attention"]
if "--slots" in argv:
    at = argv.index("--slots")
    cfg["engine"]["num_slots"] = int(argv[at + 1])
    del argv[at:at + 2]
seeds = [int(s) for s in argv[0].split(",")]
names = argv[1].split(",")

real = dict(route=moe_mod._route, ring=decode_mod.ring_coords,
            ragged=decode_mod.ragged_paged_attention_pallas,
            oracle=decode_mod.ragged_attention_reference)


def _route_with(old, new):
    src = inspect.getsource(real["route"])
    assert old in src, old
    ns = dict(moe_mod.__dict__)
    exec(src.replace(old, new), ns)
    return ns["_route"]


def sink_everywhere(fn):
    """The kernel (or its oracle) told a sink of 4 a head wherever its caller
    names none: the full layers' calls."""
    def call(q, *a, sink=None, **kw):
        return fn(q, *a, sink=jnp.full((q.shape[1],), 4.0, jnp.float32)
                  if sink is None else sink, **kw)
    return call


def ring_row_at_pos_mod_window(seg, pos, ring, table_entries):
    return real["ring"](seg, pos % cfg["sliding_window"], ring,
                        table_entries)


def window_trees(params, edit):
    return dict(params, window_layers=tuple(
        edit(dict(t)) for t in params["window_layers"]))


def no_sink(tree):
    return dict(tree, sink=jnp.full_like(tree["sink"], -1e9))


def dense_at_experts_width(params):
    dense, wid = dict(params["dense_layers"]), cfg["moe_intermediate_size"]
    for name in ("w_gate", "w_up"):
        dense[name] = dense[name][..., :wid]
    dense["w_down"] = dense["w_down"][:, :wid]
    return dict(params, dense_layers=dense)


class ShortRing(mimo.MiMoV2FlashConfig):
    """A configuration whose ENGINE sizes the rings for a window 33 keys
    shorter (one block fewer) while the programs keep the window."""
    true_window = None

    @property
    def swa(self):
        return super().swa._replace(window=self.true_window)


def short_ring(config):
    short = ShortRing(**{f.name: getattr(config, f.name)
                         for f in dataclasses.fields(config)})
    short.true_window = config.sliding_window
    # (serve()'s blocks are 32 rows: the window and a block fewer)
    short.sliding_window = config.sliding_window - 33
    return short


VARIANTS = {
    "as_served": {},
    "forward": {"forward": True},
    "sink_dropped": {"tree": lambda p: window_trees(p, no_sink)},
    "sink_on_full_layers": {"ragged": sink_everywhere},
    "window_127": {"config": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window - 1)},
    "window_129": {"config": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window + 1)},
    "thetas_swapped": {"config": lambda c: dataclasses.replace(
        c, rope_theta=c.swa_rope_theta, swa_rope_theta=c.rope_theta)},
    "whole_head_rotated": {"config": lambda c: dataclasses.replace(
        c, partial_rotary_factor=1.0)},
    "value_scale_dropped": {"config": lambda c: dataclasses.replace(
        c, attention_value_scale=1.0)},
    "ring_one_block_short": {"config": short_ring},
    "ring_row_at_pos_mod_128": {"ring": ring_row_at_pos_mod_window},
    "bias_in_weights": {"route": _route_with(
        "w = jnp.take_along_axis(probs, idx, axis=-1)",
        "w = jnp.take_along_axis(probs + router_bias.astype(jnp.float32), "
        "idx, axis=-1)")},
    "weights_not_renormalised": {"config": lambda c: dataclasses.replace(
        c, norm_topk_prob=False)},
    "dense_layer_at_experts_width": {"tree": dense_at_experts_width},
    "fp8_weights": {"weights": True},       # last: it rebuilds the model
}


def build(seed):
    paddle.seed(seed)
    m = mimo.MiMoV2FlashForCausalLM(mimo.MiMoV2FlashConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in m.parameters()])
    return m


def serve(model, prompts):
    eng = ContinuousBatchingEngine(
        model, jit_cache={}, **common.serve_engine_kwargs(cfg["engine"]))
    seqs = [eng.submit(GenerationRequest(
        p, max_new_tokens=check["max_tokens"])) for p in prompts]
    while eng.has_work():
        eng.step()
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


def restore(model, config):
    """The program as it is: the check's own forward (positions no program
    ran) is the sound one."""
    moe_mod._route = real["route"]
    decode_mod.ring_coords = real["ring"]
    decode_mod.ragged_paged_attention_pallas = real["ragged"]
    decode_mod.ragged_attention_reference = real["oracle"]
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
        moe_mod._route = v.get("route", real["route"])
        decode_mod.ring_coords = v.get("ring", real["ring"])
        if "ragged" in v:
            decode_mod.ragged_paged_attention_pallas = v["ragged"](
                real["ragged"])
            decode_mod.ragged_attention_reference = v["ragged"](
                real["oracle"])
        record = RoutingRecord()
        model.routing_record = record
        if "tree" in v:
            params, tied = type(model).decode_params(model)
            wrong = v["tree"](params)
            model.decode_params = lambda p=wrong, t=tied: (p, t)
        if "config" in v:
            model.config = v["config"](config)
        if v.get("weights"):
            # in place, a matrix at a time: two copies of the weights and the
            # engine's caches do not fit
            for pname in [n for n, _ in model.named_parameters()]:
                val = getattr(model, pname).value
                if val.ndim < 2 or pname.endswith(("router_bias", "sink")):
                    continue
                setattr(model, pname, None)
                setattr(model, pname, Parameter(fp8(val)))
                del val
        jax.clear_caches()
        t = time.time()
        if v.get("forward"):
            served = forward_picks(model, prompts)
        else:
            served = serve(model, prompts)
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
