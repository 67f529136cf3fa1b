"""Kind ``serve``: the HTTP serving path, driven from the client's side.

Two halves in one file. ``drive()`` runs in the benchmark's parent (no JAX):
it is the load generator and the clock. ``child_main()`` is the process that
owns the chip: it builds the model from the configuration file, warms the
shapes the cell's mix can reach, calls ``paddle_tpu.serving.server.serve``
with the engine geometry of the file and every other argument at the
server's default, and then obeys one-line commands on its stdin (compile
counts, the reference check, start / stop of the device trace, finish).

Nothing here names a cell, a configuration, a mix or a metric.
"""
import http.client
import json
import os
import subprocess
import sys
import threading
import time
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import proc  # noqa: E402
import trafficgen  # noqa: E402
import window  # noqa: E402

HTTP_TIMEOUT_S = 600        # a cold first run compiles under a request
TRACE_S = 3.0               # the device trace covers this much of the window


# ===================================================================== parent
def parse_prometheus(text):
    """{family: {label-string: value}} of a Prometheus text body."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        head, _, val = ln.rpartition(" ")
        name, brace, labels = head.partition("{")
        try:
            out.setdefault(name, {})[brace + labels] = float(val)
        except ValueError:
            pass
    return out


def family_sum(scrape, name):
    return sum(scrape.get(name, {}).values())


def _get(url, timeout=HTTP_TIMEOUT_S):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


class LoadGenerator:
    """Sends the schedule and records, per request, the client-clock time
    of every streamed token. One thread per request in flight."""

    def __init__(self, base_url, schedule, mix):
        u = urllib.parse.urlparse(base_url)
        self.host, self.port = u.hostname, u.port
        self.schedule, self.mix = schedule, mix
        self.records = []
        self.stop = threading.Event()
        self._lock = threading.Lock()
        self._conns = set()
        self._threads = []
        self._next = 0

    def _new_record(self, req, due):
        rec = {"index": req["index"], "due": due, "sent": None,
               "status": None, "token_times": [], "tokens": [],
               "finish": None, "prompt_len": len(req["prompt"]),
               "max_tokens": req["max_tokens"]}
        with self._lock:
            self.records.append(rec)
        return rec

    def _send(self, req, rec):
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"],
                           "stream": bool(self.mix.get("stream", True))})
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=HTTP_TIMEOUT_S)
        with self._lock:
            self._conns.add(conn)
        try:
            rec["sent"] = time.monotonic()
            conn.request("POST", "/v1/completions", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            rec["status"] = resp.status
            if resp.status != 200:
                rec["finish"] = "refused"
                return
            if not self.mix.get("stream", True):
                ch = json.loads(resp.read())["choices"][0]
                now = time.monotonic()
                rec["tokens"] = ch["token_ids"]
                rec["token_times"] = [now] * len(ch["token_ids"])
                rec["finish"] = ch["finish_reason"]
                return
            for raw in resp:
                now = time.monotonic()
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                data = line[len("data: "):]
                if data == "[DONE]":
                    rec["finish"] = rec["finish"] or "cut"
                    return
                ch = json.loads(data)["choices"][0]
                if ch["token_id"] is not None:
                    rec["token_times"].append(now)
                    rec["tokens"].append(ch["token_id"])
                if ch["finish_reason"] is not None:
                    rec["finish"] = ch["finish_reason"]
            rec["finish"] = rec["finish"] or "cut"
        except (OSError, ValueError, http.client.HTTPException):
            # our own close at the window's end lands here too
            rec["finish"] = "abandoned" if self.stop.is_set() else "error"
        finally:
            with self._lock:
                self._conns.discard(conn)
            conn.close()

    def _open_loop(self, t0):
        for req in self.schedule:
            wait = t0 + req["due"] - time.monotonic()
            if wait > 0 and self.stop.wait(wait):
                return
            if self.stop.is_set():
                return
            rec = self._new_record(req, t0 + req["due"])
            th = threading.Thread(target=self._send, args=(req, rec),
                                  daemon=True)
            th.start()
            self._threads.append(th)

    def _closed_client(self):
        while not self.stop.is_set():
            with self._lock:
                if self._next >= len(self.schedule):
                    return
                req = self.schedule[self._next]
                self._next += 1
            rec = self._new_record(req, time.monotonic())
            self._send(req, rec)

    def start(self, t0):
        if self.mix["loop"] == "open":
            ths = [threading.Thread(target=self._open_loop, args=(t0,),
                                    daemon=True)]
        else:
            ths = [threading.Thread(target=self._closed_client, daemon=True)
                   for _ in range(int(self.mix["clients"]))]
        for th in ths:
            th.start()
        self._threads.extend(ths)

    def abandon(self):
        """Stop sending; close every connection still streaming (the
        server cancels on disconnect); wait for the threads."""
        self.stop.set()
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                if c.sock is not None:
                    c.sock.shutdown(2)
            except OSError:
                pass
        for th in list(self._threads):
            th.join(10)


class Child:
    """The serving child and its line protocol."""

    def __init__(self, cmd, env, err_path):
        self.err_path = err_path
        self.p = proc.spawn(cmd, env, err_path, stdin=subprocess.PIPE)

    def read(self, timeout_s):
        box = {}

        def rd():
            while True:
                line = self.p.stdout.readline()
                if not line:
                    return
                if line.startswith("{"):
                    box["doc"] = json.loads(line)
                    return

        th = threading.Thread(target=rd, daemon=True)
        th.start()
        th.join(timeout_s)
        if "doc" not in box:
            raise RuntimeError(
                f"serving child said nothing (exit code {self.p.poll()})\n"
                + proc.err_tail(self.err_path))
        return box["doc"]

    def ask(self, command, payload=None, timeout_s=HTTP_TIMEOUT_S):
        self.p.stdin.write(json.dumps({"cmd": command,
                                       "payload": payload}) + "\n")
        self.p.stdin.flush()
        return self.read(timeout_s)


def drive(ctx):
    """Run one serving cell; returns the sources the metric readers read."""
    mix, args, log = ctx["mix"], ctx["args"], ctx["log"]
    rehearse = args.rehearse_cpu
    if rehearse:
        mix = {**mix, **mix.get("rehearse", {})}
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child",
           "--config", ctx["config_path"], "--traffic", ctx["mix_path"],
           "--seed", str(args.seed), "--trace", str(args.trace),
           "--run-dir", ctx["run_dir"]]
    if rehearse:
        cmd.append("--rehearse-cpu")
    child = Child(cmd, proc.child_env(rehearse, ctx["chips"]),
                  os.path.join(ctx["run_dir"], "child.err"))
    gen = None
    try:
        ready = child.read(ctx["setup_budget_s"])
        base = ready["url"]
        log({"event": "ready", **{k: v for k, v in ready.items()
                                  if k != "url"}})
        vocab = ready["vocab_size"]

        # ---- correct, part 1: served tokens for the reference to judge
        check = mix["check"]
        prompts = trafficgen.check_prompts(check, args.seed, vocab)
        served = [None] * len(prompts)

        def ask_check(i):
            req = urllib.request.Request(
                base + "/v1/completions",
                data=json.dumps({"prompt": prompts[i],
                                 "max_tokens": check["max_tokens"]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
                served[i] = json.loads(r.read())["choices"][0]["token_ids"]

        ths = [threading.Thread(target=ask_check, args=(i,))
               for i in range(len(prompts))]
        for th in ths:
            th.start()
        for th in ths:
            th.join(HTTP_TIMEOUT_S)
        if any(s is None or len(s) != check["max_tokens"] for s in served):
            raise RuntimeError(f"check requests failed: {served}")
        verdict = child.ask("check", {
            "prompts": prompts, "served": served,
            "tolerance": check["tolerance"],
            "max_prompt_tokens": check["prompt_tokens"]["max"]})
        log({"event": "reference_check", **verdict})

        # ---- ramp, then the window
        ramp_s = float(mix["ramp_s"])
        horizon = ramp_s + args.seconds + 5.0
        schedule = trafficgen.serve_schedule(mix, args.seed, horizon, vocab)
        gen = LoadGenerator(base, schedule, mix)
        t0 = time.monotonic()
        win = (t0 + ramp_s, t0 + ramp_s + args.seconds)
        gen.start(t0)
        time.sleep(max(win[0] - time.monotonic(), 0))
        setup_s = time.monotonic() - ctx["t_process_start"]
        scrapes = [(time.monotonic(),
                    parse_prometheus(_get(base + "/metrics")))]
        compile_start = child.ask("stats")
        traced = bool(args.trace)
        trace_at_s, traced_req = trafficgen.trace_start_s(
            schedule, mix, ramp_s, args.seconds, TRACE_S)
        trace_at = t0 + trace_at_s
        trace_window = trace_took = None
        next_scrape = win[0] + 1.0
        while True:
            now = time.monotonic()
            if now >= win[1]:
                break
            if traced and trace_window is None and now >= trace_at \
                    and args.seconds > TRACE_S:
                child.ask("trace_start")
                started = time.monotonic()
                time.sleep(TRACE_S)
                child.ask("trace_stop")
                trace_window = TRACE_S
                trace_took = [started - now,
                              time.monotonic() - started - TRACE_S]
                continue
            if traced and now >= next_scrape:
                scrapes.append((now, parse_prometheus(
                    _get(base + "/metrics"))))
                next_scrape += 1.0
                continue
            time.sleep(min(0.05, max(win[1] - now, 0)))
        scrapes.append((time.monotonic(),
                        parse_prometheus(_get(base + "/metrics"))))
        compile_end = child.ask("stats")
        spans = json.loads(_get(base + "/debug/trace")) if traced else None
        gen.abandon()

        # ---- after the window
        health = json.loads(_get(base + "/healthz"))
        profile = None
        if traced:
            profile = json.loads(_get(base + "/debug/profile?memory=1"))
        final = child.ask("finish", timeout_s=300)
        rc = child.p.wait(120)
        if rc != 0:
            raise RuntimeError(f"serving child exited {rc}\n"
                               + proc.err_tail(child.err_path))
    finally:
        if gen is not None:
            gen.stop.set()
        proc.reap(child.p)

    records = gen.records
    first, last = scrapes[0][1], scrapes[-1][1]
    compiles_in_window = (
        family_sum(last, "serving_program_compiles_total")
        - family_sum(first, "serving_program_compiles_total")
        + compile_end["cache_hits"] + compile_end["cache_misses"]
        - compile_start["cache_hits"] - compile_start["cache_misses"])
    faults = family_sum(last, "serving_faults_total")
    att = window.attempted(records, win)
    bad = window.failed(records, win)
    late = [(r["sent"] - r["due"]) * 1e3 for r in records
            if r["sent"] is not None and mix["loop"] == "open"]
    ttfts, gaps = window.ttfts_ms(records, win), window.gaps_ms(records, win)
    log({"event": "window", "attempted": len(att), "failed": len(bad),
         "requests_sent": len(records), "ttft_samples": len(ttfts),
         "gap_samples": len(gaps),
         "generator_lateness_p95_ms": window.percentile(late, 95),
         "ttft_ms_p50_p95": [window.percentile(ttfts, 50),
                             window.percentile(ttfts, 95)],
         "gap_ms_p50_p95": [window.percentile(gaps, 50),
                            window.percentile(gaps, 95)],
         "out_tokens_per_s": window.tokens_in_window(records, win)
         / args.seconds, "setup_s": setup_s,
         "compiles_in_window": compiles_in_window,
         "compile_whole_run": final["compile"],
         "engine_restarts": health["engine_restarts"], "faults": faults,
         "backlog_mid_end": _backlog(scrapes, win),
         **({"trace_at_s": trace_at_s,
             "trace_start_stop_took_s": trace_took,
             "traced_request": traced_req and {
                 k: traced_req[k] for k in ("index", "due", "max_tokens")}}
            if trace_window else {})})
    correct = (verdict["ok"] and compiles_in_window == 0
               and health["engine_restarts"] == 0 and faults == 0
               and len(att) > 0)
    compared = {"worst_margin_share": (verdict["worst_margin_share"],
                                       verdict["tolerance"]),
                "compiles_in_window": (compiles_in_window, 0),
                "engine_restarts": (health["engine_restarts"], 0),
                "faults": (faults, 0)}
    if "worst_expert_margin" in verdict:
        compared["worst_expert_margin"] = (verdict["worst_expert_margin"],
                                           verdict["expert_margin"])
    return {
        "correct": bool(correct), "attempted": len(att), "failed": len(bad),
        "compared": compared,
        "setup_s": setup_s, "window": win, "client": records,
        "metrics_delta": {"start": first, "end": last, "scrapes": scrapes},
        "span_export": spans, "debug_profile": profile,
        "xplane": final.get("xplane"), "trace_window_s": trace_window,
        "child": final, "device": final["device"], "mix": mix,
        "model": ready["model"], "engine": ready["engine"],
    }


def _backlog(scrapes, win):
    """Requests waiting or running at the window's middle and end: an
    open-loop rate is sustained when the second is no larger."""
    def load(s):
        return family_sum(s, "serving_queue_depth") \
            + family_sum(s, "serving_active_slots")
    mid_t = (win[0] + win[1]) / 2.0
    mid = min(scrapes, key=lambda ts: abs(ts[0] - mid_t))
    return [load(mid[1]), load(scrapes[-1][1])]


# ====================================================================== child
def _warm_engine(model, geometry, mix, vocab, log):
    """Compile and run, before the server exists, every program the mix can
    reach. Prompts no longer than ``prefill_chunk`` are prefilled whole by a
    program specialised on (group size, length bucket), both powers of two,
    so the grid the mix's clip range spans is walked; longer prompts and
    all decoding share the one unified step. The engine here is the class
    ``serve()`` builds, with ``serve()``'s own defaults, sharing the model's
    jit cache, so the server's engine finds every program traced."""
    import gc

    from kinds import common
    from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest

    engine = ContinuousBatchingEngine(
        model, jit_cache=model.__dict__.setdefault("_serving_jit", {}),
        **common.serve_engine_kwargs(geometry))
    chunk = engine.prefill_chunk or engine.max_seq_len
    spans = [mix["prompt_tokens"], mix["check"]["prompt_tokens"]]
    lo = min(s.get("min", s.get("value")) for s in spans)
    hi = max(s.get("max", s.get("value")) for s in spans)
    # one length in every power-of-two bucket between lo and hi
    short = sorted({lo, min(hi, chunk)}
                   | {1 << k for k in range(lo.bit_length(), 31)
                      if (1 << k) <= min(hi, chunk)}) if lo <= chunk else []
    # group sizes are padded to powers of two: one group in every pad size
    groups = sorted({min(1 << i, engine.num_slots)
                     for i in range(engine.num_slots.bit_length() + 1)})
    shapes = []
    import random
    rng = random.Random(0)

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    for n in short:
        for g in groups:
            engine.generate([GenerationRequest(prompt(n), max_new_tokens=2)
                             for _ in range(g)])
            shapes.append(["whole", g, n])
    if hi > chunk:
        n = min(hi, engine.max_seq_len - 4)
        engine.generate([GenerationRequest(prompt(n), max_new_tokens=2)])
        shapes.append(["chunked", 1, n])
    del engine
    gc.collect()
    log(f"warmed {len(shapes)} shapes")
    return shapes


def _reference_check(model, payload, log):
    """``correct`` for serving, judged on logits and not on tokens: with
    random weights the largest logit changes hands on rounding, so a served
    token t at position i passes when the float32 reference, teacher-forced
    over prompt + served tokens, ranks it within ``tolerance`` of its own
    best: max_j ref[i, j] - ref[i, t] <= tolerance * max_j |ref[i, j]|."""
    import numpy as np

    import reference
    prompts, served = payload["prompts"], payload["served"]
    n_out = len(served[0])
    # one shape whatever the seed drew, so the reference compiles once per
    # checkout and not once per seed; causal, so the padding changes nothing
    width = payload["max_prompt_tokens"] + n_out
    ids = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        ids[i, :len(p) + n_out] = list(p) + list(s)
    # position len(p) - 1 + k predicts served token k
    at = np.asarray([[len(p) - 1 + k for k in range(n_out)]
                     for p in prompts], np.int32)
    weights = reference.weights_of(model)
    logits = np.asarray(reference.logits_at(
        weights, reference.hyper_of(model.config), ids, at))
    worst = 0.0
    for i, s in enumerate(served):
        for k, t in enumerate(s):
            row = logits[i, k]
            worst = max(worst, float((row.max() - row[t])
                                     / max(np.abs(row).max(), 1e-9)))
    ok = bool(np.isfinite(logits).all()) and worst <= payload["tolerance"]
    return {"ok": ok, "worst_margin_share": worst,
            "tolerance": payload["tolerance"],
            "tokens_judged": int(len(served) * n_out)}


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

    def log(msg):
        print(f"[serve child +{time.monotonic() - t_start:.1f}s] {msg}",
              file=sys.stderr, flush=True)

    t_start = time.monotonic()
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving.server import serve

    paddle.seed(a.seed)
    model = LlamaForCausalLM(LlamaConfig(**common.model_keys(cfg),
                                         dtype=cfg["dtype"]))
    jax.block_until_ready([p.value for p in model.parameters()])
    log("model built")
    t_model = time.monotonic() - t_start
    geometry = dict(cfg["engine"])
    shapes = _warm_engine(model, geometry, mix, cfg["vocab_size"], log)
    t_warm = time.monotonic() - t_start
    server = serve(model, port=0, trace=bool(a.trace), **geometry)
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
            common.say(_reference_check(model, msg["payload"], log))
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
