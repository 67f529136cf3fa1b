"""Kind ``serve_arch``: kind ``serve`` for any model the configuration
names. The parent half (load generator, clock, line protocol) and the
warm-up are ``kinds/serve.py``'s own, by import and not by copy: that file
is loaded here as a module whose ``__file__`` is this file, so its
``drive()`` starts THIS file as the child. The child differs in two places
only: the model's class and configuration class come from the configuration
file (``"model": {"module", "config", "class"}``), and so does the plain
reference that decides ``correct`` (``"reference"``, a module beside
``reference.py`` with the same three entry points).

Where the reference can return the router's probabilities (a model with a
routed FFN), the check also judges the routing: every expert the system
picked, in its own whole-sequence forward in the served dtype at the judged
positions, must have a reference probability within ``expert_margin`` (the
mix's ``check``) of the reference's ``top_k``-th largest, and the share of
(layer, position) pairs whose expert SETS differ is reported. Under bf16 the
hidden state carries rounding error, so near the boundary between the
``top_k``-th and the next expert the two may pick differently; the picked
weights are then nearly equal and the logits barely move, which the margin
allows and a wrong router does not.

The next ``benchmark`` issue should fold the two kinds into one.
"""
import importlib
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def _load_serve():
    spec = importlib.util.spec_from_file_location(
        "bench_serve_for_arch", os.path.join(HERE, "kinds", "serve.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.__file__ = os.path.abspath(__file__)    # the child it spawns
    return mod


serve = _load_serve()
drive = serve.drive


def _reference_check(model, reference, payload, check):
    """``serve._reference_check``'s rule on logits, by the configuration's
    reference module, plus the routing's agreement (module docstring)."""
    import numpy as np

    prompts, served = payload["prompts"], payload["served"]
    n_out = len(served[0])
    width = payload["max_prompt_tokens"] + n_out
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        ids[i, :len(p) + n_out] = list(p) + list(s)
    at = np.asarray([[len(p) - 1 + k for k in range(n_out)]
                     for p in prompts], np.int32)
    weights = reference.weights_of(model)
    hyper = reference.hyper_of(model.config)
    routed = "top_k" in hyper
    out = reference.logits_at(weights, hyper, ids, at,
                              **({"with_router": True} if routed else {}))
    logits = np.asarray(out[0] if routed else out)
    worst = 0.0
    for i, s in enumerate(served):
        for k, t in enumerate(s):
            row = logits[i, k]
            worst = max(worst, float((row.max() - row[t])
                                     / max(np.abs(row).max(), 1e-9)))
    doc = {"worst_margin_share": worst, "tolerance": payload["tolerance"],
           "tokens_judged": int(len(served) * n_out)}
    ok = bool(np.isfinite(logits).all()) and worst <= payload["tolerance"]
    if routed:
        probs = np.asarray(out[1])                      # [L, B, K, E]
        _, picks = model.forward(ids, return_router_picks=True)
        picks = np.take_along_axis(np.asarray(picks),   # [L, B, K, top_k]
                                   at[None, :, :, None], axis=2)
        k = hyper["top_k"]
        kth = np.sort(probs, -1)[..., -k][..., None]
        picked = np.take_along_axis(probs, picks, -1)
        short = float(((kth - picked) / kth).max())     # <= 0: in the set
        ref_sets = np.sort(np.argsort(probs, -1)[..., -k:], -1)
        differ = float((np.sort(picks, -1) != ref_sets).any(-1).mean())
        doc.update(worst_expert_margin=max(short, 0.0),
                   expert_margin=check["expert_margin"],
                   expert_sets_differ_share=differ,
                   routings_judged=int(picks[..., 0].size))
        ok = ok and short <= check["expert_margin"]
    return {"ok": ok, **doc}


def child_main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args(argv)
    sys.path.insert(0, serve.proc.ROOT)
    from kinds import common
    with open(a.config) as f:
        cfg = json.load(f)
    with open(a.traffic) as f:
        mix = json.load(f)
    if a.rehearse_cpu:
        cfg = {**cfg, **cfg["rehearse"]}
        mix = {**mix, **mix.get("rehearse", {})}
    # a program without the model (a parent commit) fails here, at once,
    # before it touches a device
    module = importlib.import_module(cfg["model"]["module"])
    reference = importlib.import_module(cfg["reference"])
    stats, devs = common.child_start(a.rehearse_cpu, cfg["chips"])

    def log(msg):
        print(f"[serve child +{time.monotonic() - t_start:.1f}s] {msg}",
              file=sys.stderr, flush=True)

    t_start = time.monotonic()
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.serving.server import serve as start_server

    paddle.seed(a.seed)
    model = getattr(module, cfg["model"]["class"])(
        getattr(module, cfg["model"]["config"])(
            **common.model_keys(cfg), dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in model.parameters()])
    log("model built")
    t_model = time.monotonic() - t_start
    geometry = dict(cfg["engine"])
    shapes = serve._warm_engine(model, geometry, mix, cfg["vocab_size"], log)
    t_warm = time.monotonic() - t_start
    server = start_server(model, port=0, trace=bool(a.trace), **geometry)
    common.say({"event": "ready", "url": server.url,
                "device": common.device_doc(devs),
                "vocab_size": cfg["vocab_size"],
                "model": common.model_keys(cfg), "engine": geometry,
                "warmed_shapes": shapes, "model_build_s": t_model,
                "warmup_done_s": t_warm, "compile": stats.snapshot()})
    trace_dir = os.path.join(a.run_dir, "xplane")
    traced = False
    for line in sys.stdin:
        msg = json.loads(line)
        cmd = msg["cmd"]
        if cmd == "stats":
            common.say(stats.snapshot())
        elif cmd == "check":
            common.say(_reference_check(model, reference, msg["payload"],
                                        mix["check"]))
        elif cmd == "trace_start":
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            common.say({"ok": True})
        elif cmd == "trace_stop":
            jax.profiler.stop_trace()
            traced = True
            common.say({"ok": True})
        elif cmd == "finish":
            out = {"device": common.device_doc(devs),
                   "compile": stats.snapshot()}
            if traced:
                import timeline
                import xplane_reduce
                out["xplane"] = xplane_reduce.reduce_dir(
                    trace_dir, timeline.SPAN_NAMES)
            server.shutdown()
            common.say(out)
            return 0
    server.shutdown()       # the parent went away
    return 1


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
