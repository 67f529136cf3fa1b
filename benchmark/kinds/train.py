"""Kind ``train``: ``jit.TrainStep`` on the decoder, started through
``python -m paddle_tpu.distributed.launch`` as a user starts it.

``drive()`` runs in the benchmark's parent (no JAX) and only waits for the
child's report. ``child_main()`` owns the chips: mesh (``fleet.init`` when
the configuration names hybrid degrees), model, AdamW, ``TrainStep``; data
through ``paddle_tpu.io.DataLoader`` over a seeded dataset of the mix's
shape; the reference's float32 loss of every token of the first batch
before the optimizer state exists; ``forward_check`` and ``update_check``
(what ``correct`` means here); warm-up steps; then the window.

In the window steps are dispatched back to back with at most two in flight:
after dispatching step k the loop waits for the loss of step k - 2, which
bounds the queue and checks every loss without ever draining the device.
The loop notes the clock each time a step's loss arrives: the intervals
between those arrivals are the steps' times as the trainer's user sees
them, and ``metrics/train_tokens_per_s.py`` judges their median.

Nothing here names a cell, a configuration, a mix or a metric.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import proc  # noqa: E402

IN_FLIGHT = 2
TRACE_STEPS = 10
ANNOTATIONS = ("data_fetch", "train_step")
# The forward pass is judged token by token, because the batch's mean loss
# cannot fail: labels are uniform random ids and the last RMSNorm fixes the
# size of the hidden state, so the mean is ln(vocab) + var(logit) / 2 = 11.2
# whatever the layers compute (REVIEW of PR 23: independent random hidden
# states move it by 0.003). One token's loss is lse(logits) - logits[label]
# with logits of standard deviation 0.02 x sqrt(4096) = 1.28: a wrong hidden
# state (a mask that is not causal, a layer skipped, heads mapped wrongly)
# moves it by about 1.8. How far bf16 may move it grows with the depth, so
# the tolerance is data: the mix's ``check.tolerance_nat``, with the
# measurements behind it beside it.
# The mean is still compared, as a guard on the loss head's reduction (a
# wrong denominator or a dropped chunk moves it by far more): a float32
# mean over thousands of tokens, PR 22 measured 2.6e-4 between two
# summation orders, the chip runs of PR 23 at most 2.5e-4.
TOL_LOSS = 2e-2


# ===================================================================== parent
def drive(ctx):
    args = ctx["args"]
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--log_dir", os.path.join(ctx["run_dir"], "launch"),
           os.path.abspath(__file__), "--child",
           "--config", ctx["config_path"], "--traffic", ctx["mix_path"],
           "--seed", str(args.seed), "--trace", str(args.trace),
           "--seconds", str(args.seconds), "--run-dir", ctx["run_dir"]]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    err_path = os.path.join(ctx["run_dir"], "child.err")
    p = proc.spawn(cmd, proc.child_env(args.rehearse_cpu, ctx["chips"]),
                   err_path)
    try:
        out, _ = p.communicate(timeout=ctx["setup_budget_s"] + args.seconds)
        if p.returncode != 0:
            raise RuntimeError(f"training child exited {p.returncode}\n"
                               + proc.err_tail(err_path))
    finally:
        proc.reap(p)
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    if not lines:
        raise RuntimeError("training child printed no report\n"
                           + proc.err_tail(err_path))
    rep = json.loads(lines[-1])
    ctx["log"]({"event": "child_report",
                **{k: v for k, v in rep.items() if k != "xplane"}})
    correct = (rep["forward_ok"] and rep["update_ok"] and rep["loss_ok"]
               and rep["losses_finite"] and rep["compiles_in_window"] == 0
               and rep["steps"] > 0)
    return {
        "correct": bool(correct), "attempted": rep["steps"],
        "failed": rep["steps_nonfinite"],
        "compared": {
            "forward_worst_nat": (rep["forward_worst"],
                                  rep["forward_tolerance"]),
            "loss0_gap_nat": (abs(rep["loss0"] - rep["ref_loss"]),
                              rep["tolerance"]),
            # the first batch's loss after step 0 less before: under 0
            "loss_change_after_step0": (rep["loss0_after_step0"]
                                        - rep["loss0"], 0),
            "steps_nonfinite": (rep["steps_nonfinite"], 0),
            "compiles_in_window": (rep["compiles_in_window"], 0)},
        "setup_s": rep["window_start_monotonic"] - ctx["t_process_start"],
        "child": rep, "xplane": rep.get("xplane"), "device": rep["device"],
        "trace_window_s": None, "mix": rep["mix"], "model": rep["model"],
    }


# ====================================================================== child
def build_model(cfg, seed):
    """(mesh or None, data-parallel replicas, model) as the configuration's
    ``trainer`` block asks: ``fleet.init`` first where it names hybrid
    degrees, then the model from the seed."""
    import paddle_tpu as paddle
    from kinds import common
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    trainer = cfg["trainer"]
    mesh, replicas = None, 1
    hybrid = trainer.get("hybrid_configs")
    if hybrid:
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = dict(hybrid)
        fleet.init(is_collective=True, strategy=strategy)
        mesh = fleet.get_hybrid_communicate_group().mesh
        shape = dict(mesh.shape)
        for axis, key in (("sharding", "sharding_degree"),
                          ("mp", "mp_degree"), ("dp", "dp_degree")):
            if shape.get(axis, 1) != hybrid.get(key, 1):
                raise SystemExit(f"mesh is {shape}, asked {hybrid}")
        replicas = shape.get("sharding", 1) * shape.get("dp", 1)
    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig(
        **common.model_keys(cfg), dtype=cfg["dtype"],
        **trainer.get("model_options", {})))
    return mesh, replicas, model


def build_step(model, cfg, mesh):
    """AdamW and ``TrainStep`` as ``chip_smoke.py`` wires them; the model's
    forward returns the loss, so the loss function passes it through."""
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    trainer = cfg["trainer"]
    opt = AdamW(parameters=model.parameters(), **trainer["optimizer"])
    return TrainStep(model, lambda loss, _lab: loss, opt, mesh=mesh,
                     sharding_stage=int(trainer.get("sharding_stage", 0)))


def eval_loss(step, ids, labels):
    """The program's own loss on a batch without an update: the forward
    pass of the compiled step (``TrainStep.eval_step``: same parameters,
    same placement, same layer body, kernels and loss head). A label of -1
    is left out of the mean, as the model's loss head defines it."""
    return float(step.eval_step((ids, labels), (ids,)).value)


def forward_check(step, ids, ref_nll, tokens):
    """The program's loss at single tokens of the first batch against the
    reference's, before any update: for each (row, position) every label
    but the one that position predicts is masked, so the mean the program
    returns is that token's loss. Returns the deltas, program - reference."""
    import numpy as np

    import paddle_tpu as paddle
    ids_np = np.asarray(ids.value)
    deltas = []
    for row, pos in tokens:
        labels = np.full(ids_np.shape, -1, ids_np.dtype)
        labels[row, pos + 1] = ids_np[row, pos + 1]
        got = eval_loss(step, ids, paddle.to_tensor(labels))
        deltas.append(got - float(ref_nll[row, pos]))
    return deltas


def child_main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args(argv)
    sys.path.insert(0, proc.ROOT)
    from kinds import common
    with open(a.config) as f:
        cfg = json.load(f)
    with open(a.traffic) as f:
        mix = json.load(f)
    if a.rehearse_cpu:
        cfg = {**cfg, **cfg["rehearse"]}
        mix = {**mix, **mix.get("rehearse", {})}
    stats, devs = common.child_start(a.rehearse_cpu, cfg["chips"])
    t_start = time.monotonic()

    def log(msg):
        print(f"[train child +{time.monotonic() - t_start:.1f}s] {msg}",
              file=sys.stderr, flush=True)

    import jax
    import numpy as np

    import paddle_tpu as paddle
    import reference
    import trafficgen
    from paddle_tpu.io import DataLoader, Dataset

    seq, vocab = int(mix["seq_len"]), cfg["vocab_size"]
    mesh, replicas, model = build_model(cfg, a.seed)
    batch = int(mix["sequences_per_replica"]) * replicas
    log("model built")

    class Seeded(Dataset):
        def __len__(self):
            return int(mix["dataset_sequences"])

        def __getitem__(self, i):
            return trafficgen.train_sample(a.seed, i, seq, vocab)

    loader = DataLoader(Seeded(), batch_size=batch, shuffle=False,
                        drop_last=True,
                        num_workers=int(cfg["trainer"].get("loader_workers",
                                                           2)))
    batches = iter(loader)

    def fetch():
        b = next(batches)
        return b[0] if isinstance(b, (list, tuple)) else b

    ids0 = fetch()
    ref_nll = np.asarray(reference.token_nll(
        reference.weights_of(model), reference.hyper_of(model.config),
        np.asarray(ids0.value)))
    ref_loss = float(ref_nll.mean())
    log(f"reference loss {ref_loss}")
    step = build_step(model, cfg, mesh)

    def run(ids):
        return step.step((ids, ids), (ids,)).value

    # correct, part 1: the forward pass, token by token, before any update
    tokens = trafficgen.train_check_tokens(a.seed, batch, seq,
                                           int(mix["check"]["tokens"]))
    tol_nat = float(mix["check"]["tolerance_nat"])
    deltas = forward_check(step, ids0, ref_nll, tokens)
    worst = max(abs(d) for d in deltas)
    log(f"forward check: worst |program - reference| {worst:.5f} nat over "
        f"{len(tokens)} tokens")
    loss0 = float(run(ids0))
    log(f"step 0 loss {loss0}")
    # correct, part 2: backward pass and optimizer. Step 0 trained on the
    # first batch; AdamW's first update moves every weight by the learning
    # rate against the sign of its gradient, so the loss on that same batch
    # (same tokens, no sampling noise) must have fallen. A gradient of the
    # wrong sign, a zero gradient or an update that is not applied fails.
    loss0_after = eval_loss(step, ids0, ids0)
    log(f"first batch after step 0: loss {loss0_after}")
    last = None
    for _ in range(int(cfg["trainer"].get("warmup_steps", 3))):
        last = run(fetch())
    last.block_until_ready()
    log("warm")

    # ---------------------------------------------------------- the window
    want_trace = bool(a.trace)
    trace_dir = os.path.join(a.run_dir, "xplane")
    if want_trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    compiled0 = stats.cache_hits + stats.cache_misses
    pending, losses, fetch_s, done_s = [], [], [], []
    t0 = time.monotonic()
    t_end = t0 + a.seconds
    trace_from = None
    while True:
        now = time.monotonic()
        if now >= t_end and trace_from is None:
            break
        k = len(losses) + len(pending)
        if want_trace and trace_from is None \
                and now >= t0 + a.seconds / 2.0:
            jax.profiler.start_trace(trace_dir)
            trace_from = k
        t_f = time.monotonic()
        with jax.profiler.TraceAnnotation("data_fetch"):
            ids = fetch()
        fetch_s.append(time.monotonic() - t_f)
        with jax.profiler.TraceAnnotation("train_step"):
            pending.append(run(ids))
        if len(pending) > IN_FLIGHT:
            losses.append(float(pending.pop(0)))
            done_s.append(time.monotonic() - t0)
        if trace_from is not None and k + 1 - trace_from >= TRACE_STEPS:
            pending[-1].block_until_ready()
            jax.profiler.stop_trace()
            trace_from = None
            want_trace = False      # one trace a run
    for x in pending:                           # blocks on the last step
        losses.append(float(x))
        done_s.append(time.monotonic() - t0)
    t1 = time.monotonic()
    compiled1 = stats.cache_hits + stats.cache_misses
    steps = len(losses)

    out = {
        "device": None, "mix": mix, "model": common.model_keys(cfg),
        "steps": steps, "tokens_per_step": batch * seq,
        "window_start_monotonic": t0, "window_s": t1 - t0,
        "tokens": steps * batch * seq, "replicas": replicas,
        "step_done_s": done_s,
        "losses_first_last": [losses[0], losses[-1]] if losses else [],
        "losses_finite": bool(np.isfinite(losses).all()),
        "steps_nonfinite": int((~np.isfinite(losses)).sum()),
        "loss0": loss0, "ref_loss": ref_loss, "tolerance": TOL_LOSS,
        "loss_ok": bool(abs(loss0 - ref_loss) <= TOL_LOSS),
        "forward_tokens": tokens, "forward_deltas": deltas,
        "forward_worst": worst, "forward_tolerance": tol_nat,
        "forward_ok": bool(np.isfinite(deltas).all() and worst <= tol_nat),
        "loss0_after_step0": loss0_after,
        "update_ok": bool(loss0_after < loss0),
        "compiles_in_window": compiled1 - compiled0,
        "data_fetch_ms_mean": 1e3 * sum(fetch_s) / max(len(fetch_s), 1),
        "compile": stats.snapshot(),
    }
    if a.trace:
        import xplane_reduce
        out["xplane"] = xplane_reduce.reduce_dir(trace_dir, ANNOTATIONS)
        blocked = []
        for _ in range(TRACE_STEPS):
            ids = fetch()
            t = time.monotonic()
            run(ids).block_until_ready()
            blocked.append(time.monotonic() - t)
        out["blocked_step_ms"] = [1e3 * x for x in blocked]
        mem = step.compile_step((ids, ids), (ids,)).memory_analysis()
        out["memory_analysis"] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes)}
    out["device"] = common.device_doc(devs)
    common.say(out)
    return 0


if __name__ == "__main__":
    sys.exit(child_main(sys.argv[1:]))
