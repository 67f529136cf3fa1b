#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

drives the two main paths once, through the entry points a user would call,
at the full width of ``llama_7b()`` (hidden 4096, 32 x 128 heads, ffn 11008,
vocab 32000, bf16) with the depth cut and random weights made from a seed:

  kernels  every Pallas kernel on the default serving and training paths
           (the routed FFN's grouped matmuls at OLMoE widths and, with
           one chip's share of the experts, at DeepSeek-V2's; the
           absorbed-form latent attention kernel at DeepSeek-V2's; the
           gated delta rule's chunked scan and decode-row update at
           Olmo-Hybrid-7B's 30 heads of 96 x 192; the three attention /
           state kernels of the unified step also at each serving cell's
           decode-only packed size, 8 / 16 / 24 / 32 rows and nothing behind;
           the ragged kernel also at 20 query heads on ONE KV head, 16 k
           into a document),
           compiled by Mosaic and RUN against its jnp reference;
  serve    ``python -m paddle_tpu.serving.server --preset llama7b-8of32``
           answering cold, chunked, concurrent and streamed requests;
  train    ``python -m paddle_tpu.distributed.launch`` taking a few
           ``jit.TrainStep`` steps of ``LlamaForCausalLM``, 2 layers;
  serve4 / train4   the same on four chips (``--tp 4``; ``fleet.init`` with
           sharding_degree=2, mp_degree=2), when JAX reports four or more.

This process imports no JAX. A chip belongs to one process at a time, so each
phase is a child that owns the chip and exits before the next starts; every
child keeps its compiled programs in one cache directory
(``paddle_tpu.utils.compile_cache``). A child that exits non-zero, a failed
check or a timeout ends the smoke at once with a non-zero exit code and no
result line; nothing is caught and carried on from. If JAX's platform is not
``tpu`` the first child exits at once. The last stdout line of a passing run
is ``{"ok": true, "device": {...}}`` with the device as JAX reports it.

``--rehearse-cpu`` is for debugging this script's own control flow in a
sandbox: ``llama_tiny`` on the CPU backend with four virtual devices, Pallas
in interpret mode. It is chosen by that argument and never by detection, and
its result line names the platform ``cpu``.
"""
import argparse
import functools
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
BUDGET_S = 1140             # the contract allows 1200 s, compilation included
HTTP_TIMEOUT_S = 420        # one request may wait on several ~15 s compiles

# (query heads, kv heads, head dim) the kernels are checked at
FULL = dict(
    geometries=[(32, 32, 128), (32, 8, 128)], flash_seq=2048,
    # the ragged kernel at the two dense serving cells' geometries: heads,
    # KV heads, head width, table entries, rows of (span, kv length)
    ragged_cells={
        "mistral chunk 32/8/128": (32, 8, 128, 128, [
            (1, 2307), (512, 3584), (1, 33), (1, 1024), (0, 0), (1, 700)]),
        "olmoe decode 16/16/128": (16, 16, 128, 64, [
            (1, 100 + 50 * i + (i * 37) % 29) for i in range(24)]),
        # Olmo-Hybrid's full layers: 30 heads, the first count that is no
        # power of two (30 planes of the head-major query, 128 tokens a
        # query block), a pool row of 3,840; a 512-token chunk 688 tokens
        # into its prompt behind the other 31 slots' decode rows
        "olmo-hybrid chunk 30/30/128": (30, 30, 128, 72, [
            (1, 520 + 55 * i + (i * 37) % 29) for i in range(31)]
            + [(512, 1200)]),
        # Nemotron-3-Nano's attention blocks: 32 query heads on 2 KV heads,
        # a query group of 16 where the widest before was 4; a 512-token
        # chunk 3 k into its prompt behind 31 decode rows at 1.5 k - 6 k
        "nemotron chunk 32/2/128": (32, 2, 128, 192, [
            (1, 1536 + 140 * i + (i * 37) % 29) for i in range(31)]
            + [(512, 3584)]),
        # Jamba2-3B's attention layers: 20 query heads on ONE KV head (a
        # group that is no power of two, a pool row of 128 lanes), tables of
        # 1,024 entries; a 512-token chunk 16 k into its document behind 15
        # decode rows at 8 k - 25 k
        "jamba chunk 20/1/128": (20, 1, 128, 1024, [
            (1, 8192 + 1100 * i + (i * 37) % 29) for i in range(15)]
            + [(512, 16896)]),
        # Qwen3-Next's full layers: 16 query heads on 2 KV heads of 256, the
        # first head wider than 128; the second chunk of a 1,000-token prompt
        # behind 127 decode rows at 0.6 k - 3 k
        "qwen3-next chunk 16/2/256": (16, 2, 256, 128, [
            (1, 600 + 19 * i + (i * 37) % 29) for i in range(127)]
            + [(488, 1000)])},
    # the cells' decode-only steps, whose packed buffer is the slots alone
    # (8 / 24 / 32 rows and nothing behind them): the chat cell's two live
    # rows of eight, less than one query block; every row live in the others
    ragged_decode_only={
        "mistral 8 rows 32/8/128": (32, 8, 128, 128, [
            (1, 2307), (0, 0), (0, 0), (1, 300), (0, 0), (0, 0), (0, 0),
            (0, 0)], 8),
        "olmoe 24 rows 16/16/128": (16, 16, 128, 64, [
            (1, 100 + 50 * i + (i * 37) % 29) for i in range(24)], 24),
        "olmo-hybrid 32 rows 30/30/128": (30, 30, 128, 72, [
            (1, 520 + 55 * i + (i * 37) % 29) for i in range(32)], 32),
        "nemotron 32 rows 32/2/128": (32, 2, 128, 192, [
            (1, 1536 + 140 * i + (i * 37) % 29) for i in range(32)], 32),
        "jamba 16 rows 20/1/128": (20, 1, 128, 1024, [
            (1, 8192 + 1100 * i + (i * 37) % 29) for i in range(16)], 16),
        "qwen3-next 128 rows 16/2/256": (16, 2, 256, 128, [
            (1, 600 + 19 * i + (i * 37) % 29) for i in range(128)], 128)},
    preset="llama7b-8of32", slots=8, max_seq_len=4096, prefill_chunk=512,
    vocab=32000, medium_prompt=300, long_prompt=700, tp=4,
    # the routed FFN at OLMoE-1B-7B widths: (hidden, experts, expert
    # width, experts a token), then (rows, live rows) of a decode step's
    # packed buffer and of a whole-prompt prefill
    moe=dict(widths=(2048, 64, 1024, 8), rows=[(536, 24), (256, 256)]),
    # DeepSeek-V2's widths: latent attention (heads, latent rank, rope-free
    # / rope / value head widths) and its routed FFN with one chip's share
    # (hidden, router width, held experts, expert width, experts a token,
    # groups, groups a token), rows as above
    # ... and its cell's decode-only step: 32 rows over 2 k - 8 k cached
    # tokens, tables of 256 entries, nothing behind the rows
    mla=dict(widths=(128, 512, 128, 64, 128), decode_only=(256, [
        (1, 2048 + 190 * i + (i * 37) % 29) for i in range(32)])),
    moe_share=dict(widths=(5120, 160, 20, 1536, 6, 8, 3),
                   rows=[(544, 32), (256, 256)]),
    # Qwen3-Next's: (hidden, router width, held experts, expert width,
    # experts a token): 64 of a plain softmax router's 512 held, ten a token,
    # renormalised; the cell's chunk step and its decode-only step
    moe_wide=dict(widths=(2048, 512, 64, 512, 10),
                  rows=[(640, 640), (128, 128)], whole_prompt=[(2048, 2000)]),
    # GLM-5.2's sparse attention: (heads, latent rank, rope-free / rope /
    # value head widths, index heads, index head width, rows selected), and
    # its cell's two packed sizes over tables of 640 entries: 16 decode rows
    # at 6 k - 19 k, and 15 of them beside a chunk that ends 16 k into its
    # prompt (128 tokens of it: the oracle up-projects 2,048 rows a query)
    dsa=dict(widths=(64, 512, 192, 64, 256, 32, 128, 2048), mb=640, rows={
        "16 decode rows": [(1, 6144 + 800 * i + (i * 37) % 29)
                           for i in range(16)],
        "chunk at 16k": [(1, 6144 + 800 * i + (i * 37) % 29)
                         for i in range(15)] + [(128, 16384)]}),
    # Olmo-Hybrid-7B's linear layers: (heads, key width, value width) of
    # the gated delta rule, the slots and the packed rows of its cell's step
    gdn=dict(widths=(30, 96, 192), slots=32, packed=544),
    # Qwen3-Next's: (value heads, key width, value width, KEY heads): 32 on
    # 16 of 128 x 128, a key width of whole lane tiles; 32 of its cell's 128
    # slots (a slot's state is 2 MiB a layer) and the cell's packed rows
    gdn_grouped=dict(widths=(32, 128, 128, 16), slots=32, packed=640),
    # Phi-4-mini-flash's Mamba layers: (d_inner, d_state) of the selective
    # scan, the slots and the packed rows of its cell's step; and its window
    # layers' call: 40 wide queries over 10 KV pairs of 128, a 512-token
    # chunk 3 k into its prompt beside 15 decode rows, window 512
    ssm=dict(widths=(5120, 16), slots=48, packed=560),
    # Nemotron-3-Nano's Mamba-2 blocks: (heads, a head's channels, groups,
    # state size), the slots and the packed rows of its cell's step
    ssd=dict(widths=(64, 64, 8, 128), slots=32, packed=544),
    ragged_window={"window 512 40/10/128": (40, 10, 128, 256, [
        (512, 3584)] + [(1, 1500 + 290 * i + (i * 37) % 29)
                        for i in range(15)], 512)},
    # MiMo-V2-Flash's two kinds of layer: 64 query heads, keys 192 wide and
    # values 128 (a KV head's key window starts at half a lane tile for every
    # odd head); 4 KV heads over the pool, 8 over a ring under a window of
    # 128 with a sink a head: (heads, KV heads, key width, value width, table
    # entries, rows, window, packed rows): a 512-token chunk 8 k into its
    # prompt behind 31 decode rows at 1 k - 28 k, and 32 decode rows alone
    ragged_widths={
        "mimo full chunk 64/4/192|128": (64, 4, 192, 128, 1024, [
            (1, 1100 + 870 * i + (i * 37) % 29) for i in range(31)]
            + [(512, 8704)], None, 544),
        "mimo full 32 rows 64/4/192|128": (64, 4, 192, 128, 1024, [
            (1, 1100 + 870 * i + (i * 37) % 29) for i in range(32)], None,
            32),
        "mimo window chunk 64/8/192|128": (64, 8, 192, 128, 1024, [
            (1, 1100 + 870 * i + (i * 37) % 29) for i in range(31)]
            + [(512, 8704)], 128, 544),
        "mimo window 32 rows 64/8/192|128": (64, 8, 192, 128, 1024, [
            (1, 100 + 870 * i + (i * 37) % 29) for i in range(32)], 128,
            32)},
    # the same geometry's pipeline across pairs (6 pages = 192 keys a
    # group): decode rows that walk one group and an odd number of groups
    # (5, or 3 under the window) in turn, so every pair's first group is
    # started by the pair before it, into the slot that pair is not
    # computing on, and a read before it landed would be NaN or a miss
    ragged_handover={"hand-over 40/10/128": (40, 10, 128, 256, [
        (1, 40 + 9 * i) if i % 2 else (1, 900 + 4 * i)
        for i in range(16)], 512)},
    train=dict(layers=2, batch=4, seq=2048, steps=4))
REHEARSAL = dict(
    geometries=[(4, 4, 32), (4, 2, 32)], flash_seq=256,
    ragged_cells={
        "chunk 4/2/32": (4, 2, 32, 8, [(1, 150), (48, 200), (1, 33), (0, 0)]),
        "decode 16/16/32": (16, 16, 32, 8, [
            (1, 20 + 9 * i) for i in range(6)]),
        "chunk 6/6/32": (6, 6, 32, 8, [(1, 70), (40, 100), (0, 0), (1, 1)]),
        "chunk 16/1/32": (16, 1, 32, 8, [(1, 70), (40, 100), (0, 0),
                                         (1, 1)]),
        "chunk 5/1/32": (5, 1, 32, 8, [(1, 70), (40, 100), (0, 0), (1, 1)]),
        "chunk 4/2/64": (4, 2, 64, 8, [(1, 70), (40, 100), (0, 0), (1, 1)])},
    ragged_decode_only={
        "8 rows 4/2/32": (4, 2, 32, 8, [
            (1, 150), (0, 0), (0, 0), (1, 33), (0, 0), (0, 0), (0, 0),
            (0, 0)], 8),
        "6 rows 6/6/32": (6, 6, 32, 8, [(1, 20 + 9 * i) for i in range(6)],
                          6)},
    preset="tiny", slots=4, max_seq_len=128, prefill_chunk=32,
    vocab=256, medium_prompt=24, long_prompt=70,
    tp=2,                                   # llama_tiny has two kv heads
    moe=dict(widths=(64, 8, 32, 2), rows=[(36, 4), (16, 16)]),
    mla=dict(widths=(4, 32, 16, 8, 16), decode_only=(8, [
        (1, 40 + 30 * i) for i in range(6)])),
    moe_share=dict(widths=(64, 8, 4, 32, 2, 2, 1), rows=[(36, 4), (16, 16)]),
    moe_wide=dict(widths=(64, 16, 4, 32, 3), rows=[(100, 100), (16, 16)],
                  whole_prompt=[(200, 190)]),
    dsa=dict(widths=(4, 32, 16, 8, 16, 4, 16, 16), mb=8, rows={
        "6 decode rows": [(1, 40 + 30 * i) for i in range(6)],
        "chunk at 200": [(1, 150), (40, 200), (0, 0), (1, 1)]}),
    gdn=dict(widths=(4, 8, 16), slots=6, packed=150),
    gdn_grouped=dict(widths=(4, 8, 16, 2), slots=6, packed=150),
    ssm=dict(widths=(256, 16), slots=6, packed=150),
    ssd=dict(widths=(4, 8, 2, 16), slots=6, packed=150),
    ragged_window={"window 40 4/2/32": (4, 2, 32, 8, [
        (48, 200), (1, 150), (1, 33), (0, 0)], 40)},
    ragged_widths={
        "full chunk 8/2/24|16": (8, 2, 24, 16, 8, [
            (1, 150), (48, 200), (1, 33), (0, 0)], None, 62),
        "window 6 rows 8/4/24|16": (8, 4, 24, 16, 8, [
            (1, 20 + 40 * i) for i in range(6)], 16, 6)},
    ragged_handover={"hand-over 4/2/32": (4, 2, 32, 24, [
        (1, 40 + 9 * i) if i % 2 else (1, 600 + 10 * i)
        for i in range(6)], 300)},
    train=dict(layers=2, batch=4, seq=64, steps=4))

# Forward outputs: kernel and reference both take bf16 inputs (8 significant
# bits, eps 2^-8 = 3.9e-3) and accumulate in f32, but round the softmax
# weights to bf16 at different points (the kernel before normalising, the
# reference after) and round the output to bf16: a few half-ulps, 2^-9 each,
# that partly average out over the contraction. So the largest deviation is
# bounded at 2e-2 of the largest reference magnitude.
TOL_FWD = 2e-2
# Gradients go through two more bf16 roundings (dS, and P again in the
# backward kernels) and a recomputed softmax: twice the forward bound.
TOL_BWD = 4e-2
# The gated delta rule's kernels: float32 in, float32 state, float32 out, the
# products at precision HIGHEST; what differs from the token-by-token
# recurrence is the chunked form's algebra and the summation order.
TOL_GDN = 2e-3
# Step-0 loss, four chips against one: the same bf16 forward with every
# hidden/ffn contraction split over mp=2 (other summation order of bf16
# partial products); the loss is an f32 mean over batch*seq tokens near
# ln(vocab) ~ 10.4, so 2e-2 absolute is about 0.2 percent.
TOL_LOSS_4CHIP = 2e-2


class SmokeFailure(Exception):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# ===================================================================== parent
def _spawn(cmd, env, log_name):
    """Start a child in its own process group; stdout is a pipe, stderr
    goes to a log under chiprun_out/ so it survives the machine. Every
    caller reaps it in a ``finally``."""
    os.makedirs(LOG_DIR, exist_ok=True)
    with open(os.path.join(LOG_DIR, log_name + ".err"), "w") as err:
        return subprocess.Popen(cmd, env=env, cwd=ROOT, stderr=err,
                                stdout=subprocess.PIPE, text=True,
                                start_new_session=True)


def _reap(p, grace_s=20):
    """Stop a child's whole process group and wait for it."""
    if p.poll() is None:
        try:
            os.killpg(p.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            p.wait(grace_s)
        except subprocess.TimeoutExpired:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()
    else:
        try:        # the leader is gone; take any straggler of its group
            os.killpg(p.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass


def _err_tail(log_name, n=2500):
    try:
        with open(os.path.join(LOG_DIR, log_name + ".err")) as f:
            return f.read()[-n:]
    except OSError:
        return ""


def _child_env(rehearse):
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=4")
    return env


def run_phase_child(name, cmd, env, deadline):
    """Run one child to its end; its last stdout line is its JSON report."""
    t0 = time.monotonic()
    p = _spawn(cmd, env, name)
    try:
        try:
            out, _ = p.communicate(timeout=max(deadline - t0, 1))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: timed out\n{_err_tail(name)}")
        check(p.returncode == 0,
              f"{name}: child exited {p.returncode}\n{_err_tail(name)}")
    finally:
        _reap(p)
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    check(lines, f"{name}: child printed no report")
    report = json.loads(lines[-1])
    report["wall_s"] = round(time.monotonic() - t0, 1)
    return report


def _get(url, timeout=60):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _complete(base, body):
    """POST /v1/completions; returns the generated token ids (blocking or
    streamed)."""
    req = urllib.request.Request(
        base + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=HTTP_TIMEOUT_S) as r:
        check(r.status == 200, f"completions answered {r.status}")
        if body.get("stream"):
            toks, done = [], False
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                data = line[len("data: "):]
                if data == "[DONE]":
                    done = True
                    break
                ch = json.loads(data)["choices"][0]
                check(ch["finish_reason"] != "error",
                      f"stream ended in error: {data}")
                if ch["token_id"] is not None:
                    toks.append(ch["token_id"])
            check(done, "stream ended without [DONE]")
        else:
            ch = json.loads(r.read())["choices"][0]
            check(ch["finish_reason"] == "length",
                  f"finish_reason {ch['finish_reason']!r}")
            toks = ch["token_ids"]
    return toks


def _metric_samples(text, name):
    """{label-string: value} of one family in a Prometheus text body."""
    out = {}
    for ln in text.splitlines():
        if ln.startswith(name) and ln[len(name):len(name) + 1] in (" ", "{"):
            labels, _, val = ln[len(name):].rpartition(" ")
            out[labels] = float(val)
    return out


def _driver_wall_seconds(text):
    """{phase: wall seconds} of ``serving_driver_seconds_total``."""
    return {labels.split('phase="')[1].split('"')[0]: v for labels, v
            in _metric_samples(text, "serving_driver_seconds_total").items()
            if 'clock="wall"' in labels}


def _drive_requests(name, base, size):
    """The smoke's traffic: every admission path once. Returns the token
    ids and the wall seconds of each request, by name."""
    rng = random.Random(0)

    def prompt(n):
        return [rng.randrange(1, size["vocab"]) for _ in range(n)]

    tokens, request_s = {}, {}

    def ask(key, body):
        t = time.monotonic()
        tokens[key] = _complete(base, body)
        request_s[key] = round(time.monotonic() - t, 2)

    # cold path: a short prompt, prefilled whole; asked twice, greedy
    short = {"prompt": prompt(12), "max_tokens": 16}
    ask("short", short)
    # cold path again, at the first prefill bucket (512 at full size)
    # long enough for flash_attention.attention to take the kernel
    ask("medium", {"prompt": prompt(size["medium_prompt"]), "max_tokens": 4})
    # longer than --prefill-chunk: chunked through the unified step
    ask("long", {"prompt": prompt(size["long_prompt"]), "max_tokens": 8})
    # two at once: one batch, two live slots
    threads = [threading.Thread(target=ask, args=(key, {
        "prompt": prompt(n), "max_tokens": 12}))
        for key, n in (("a", 20), ("b", 33))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(HTTP_TIMEOUT_S + 30)
    check("a" in tokens and "b" in tokens, f"{name}: a concurrent request "
          f"failed or hung: got {sorted(tokens)}\n" + _err_tail(name))
    ask("streamed", {"prompt": prompt(9), "max_tokens": 10, "stream": True})
    ask("sampled", {"prompt": prompt(16), "max_tokens": 16,
                    "temperature": 1.0, "seed": 7})
    ask("short_again", short)

    asked = {"short": 16, "medium": 4, "long": 8, "a": 12, "b": 12,
             "streamed": 10, "sampled": 16, "short_again": 16}
    for key, n in asked.items():
        toks = tokens[key]
        check(len(toks) == n,
              f"{name}: {key} returned {len(toks)} tokens, asked {n}")
        check(all(isinstance(x, int) and 0 <= x < size["vocab"]
                  for x in toks), f"{name}: {key} token out of range")
    check(tokens["short_again"] == tokens["short"],
          f"{name}: same greedy request, other tokens: {tokens}")
    every = [x for toks in tokens.values() for x in toks]
    check(len(set(every)) > 1 and len(set(tokens["sampled"])) > 1,
          f"{name}: all tokens equal: {tokens}")
    return tokens, request_s


def serve_phase(name, size, env, deadline, rehearse, tp=1):
    """The server as a child, asked over HTTP with urllib only."""
    t0 = time.monotonic()
    cmd = [sys.executable, "-u", "-m", "paddle_tpu.serving.server",
           "--preset", size["preset"], "--port", "0", "--quiet",
           "--num-slots", str(size["slots"]),
           "--max-seq-len", str(size["max_seq_len"]),
           "--prefill-chunk", str(size["prefill_chunk"]), "--tp", str(tp)]
    p = _spawn(cmd, env, name)
    try:
        banner = {}

        def read_banner():
            line = p.stdout.readline()
            if line.startswith("{"):
                banner.update(json.loads(line))

        t = threading.Thread(target=read_banner, daemon=True)
        t.start()
        t.join(max(deadline - time.monotonic(), 1))
        check(banner, f"{name}: no banner (server exit code {p.poll()})\n"
              + _err_tail(name))
        want = "cpu" if rehearse else "tpu"
        check(banner["device"]["platform"] == want
              and banner["decode_attention"] == "pallas"
              and banner["pallas_interpret"] is rehearse
              and banner["tp"] == tp,
              f"{name}: wrong path in effect: {banner}")
        base = banner["listening"]
        driver0 = _driver_wall_seconds(_get(base + "/metrics"))
        t_driver0 = time.monotonic()
        tokens, request_s = _drive_requests(name, base, size)

        # a Mosaic or out-of-memory error inside engine.step() is caught by
        # the gateway, which rebuilds the engine up to eight times: without
        # these two checks it would look like a slow success
        health = json.loads(_get(base + "/healthz"))
        metrics = _get(base + "/metrics")
        t_metrics = time.monotonic()
        faults = _metric_samples(metrics, "serving_faults_total")
        check(health["status"] == "ok" and health["engine_restarts"] == 0
              and sum(faults.values()) == 0,
              f"{name}: engine faulted: {health} {faults}\n"
              + _err_tail(name))
        mem = json.loads(_get(base + "/debug/profile?memory=1",
                              timeout=HTTP_TIMEOUT_S))["memory"]
        # the requests chunk and decode, so both packed sizes of the unified
        # step were reached: the slots' rows alone, and with the chunk's room
        steps = {k: v for k, v in mem.items() if k.startswith("ragged[")}
        check(len(steps) == 2
              and not any("error" in v for v in steps.values()),
              f"{name}: expected the unified step at its two packed sizes: "
              f"{mem}")
        check(all("pallas" in k for k in mem if k.startswith("ragged[")),
              f"{name}: step program is not the pallas one: {list(mem)}")

        # the driver thread's phase clock is always on: ten phases on two
        # clocks, and the wall phases partition the thread's time (both
        # scrapes find the server idle, a mark every 20 ms)
        driver = _driver_wall_seconds(metrics)
        elapsed = t_metrics - t_driver0
        charged = sum(driver.values()) - sum(driver0.values())
        check(len(driver) == 10 and len(_metric_samples(
            metrics, "serving_driver_seconds_total")) == 20
            and abs(charged - elapsed) <= 0.02 * elapsed,
            f"{name}: the driver clock's wall phases sum to {charged:.3f} s "
            f"of {elapsed:.3f} s elapsed: {driver}")

        def per_device(family):     # {device="3"} 123 -> {"3": 123}
            return {labels.split('"')[1]: int(v) for labels, v
                    in _metric_samples(metrics, family).items()}

        peak = per_device("serving_device_peak_bytes_in_use")
        in_use = per_device("serving_device_bytes_in_use")
        if not rehearse:    # the CPU backend reports no memory statistics
            check(len(in_use) >= tp and
                  sum(v > 0 for v in in_use.values()) >= tp,
                  f"{name}: memory in use on fewer than {tp} devices: "
                  f"{in_use}")
        if tp > 1:
            coll = _metric_samples(metrics, "serving_collective_bytes_total")
            check(sum(coll.values()) > 0,
                  f"{name}: no collective bytes counted at tp={tp}: {coll}")

        def one(fam):
            return next(iter(_metric_samples(metrics, fam).values()))

        report = {
            "phase": name, "tp": tp, "device": banner["device"],
            "decode_attention": banner["decode_attention"],
            "compile_cache": banner["compile_cache"],
            "compile_s": round(one("serving_compile_seconds_total"), 1),
            "cache_hits": int(one("serving_compile_cache_hits_total")),
            "cache_misses": int(one("serving_compile_cache_misses_total")),
            "step_programs": steps, "programs": sorted(mem),
            "driver_wall_s": {k: round(v - driver0.get(k, 0.0), 3)
                              for k, v in driver.items()},
            "peak_bytes_in_use": peak,
            "engine_restarts": health["engine_restarts"],
            "request_s": request_s, "tokens": tokens}

        os.killpg(p.pid, signal.SIGTERM)    # the server drains and exits 0
        try:
            rc = p.wait(90)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: server did not stop on SIGTERM")
        check(rc == 0, f"{name}: server exited {rc}\n{_err_tail(name)}")
    finally:
        _reap(p)
    report["wall_s"] = round(time.monotonic() - t0, 1)
    return report


def parent(rehearse):
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S
    size = REHEARSAL if rehearse else FULL
    env = _child_env(rehearse)
    me = os.path.abspath(__file__)
    flag = ["--rehearse-cpu"] if rehearse else []
    launch = [sys.executable, "-m", "paddle_tpu.distributed.launch",
              "--log_dir", os.path.join(LOG_DIR, "launch")]
    reports = []

    def done(report):
        print(json.dumps(report), flush=True)
        reports.append(report)
        return report

    kernels = done(run_phase_child(
        "kernels", [sys.executable, me, "--phase", "kernels"] + flag, env,
        deadline))
    device = kernels["device"]
    check(device["platform"] == ("cpu" if rehearse else "tpu"),
          f"platform is {device['platform']!r}")
    done(serve_phase("serve", size, env, deadline, rehearse))
    train = done(run_phase_child(
        "train", launch + [me, "--phase", "train"] + flag, env, deadline))
    if device["count"] >= 4:
        done(serve_phase("serve4", size, env, deadline, rehearse,
                         tp=size["tp"]))
        done(run_phase_child(
            "train4", launch + [me, "--phase", "train4", "--ref-loss",
                                repr(train["losses"][0])] + flag,
            env, deadline))
    for r in reports:
        check(r["device"] == device, f"{r['phase']}: ran on {r['device']}, "
              f"the smoke on {device}")
    print(json.dumps({
        "phases": [r["phase"] for r in reports],
        "wall_s": round(time.monotonic() - t_start, 1),
        "compile_s": round(sum(r["compile_s"] for r in reports), 1),
        "cache_hits": sum(r["cache_hits"] for r in reports),
        "cache_misses": sum(r["cache_misses"] for r in reports),
        "versions": kernels["versions"]}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# =================================================================== children
def _child_start(rehearse):
    """Every child: fail at once off the chip, place the compile cache, and
    name the device as JAX reports it."""
    import jax
    dev = jax.devices()[0]
    if not rehearse and dev.platform != "tpu":
        print(f"chip_smoke: JAX platform is {dev.platform!r}, not 'tpu'",
              file=sys.stderr)
        sys.exit(3)
    from paddle_tpu.core.device import device_summary
    from paddle_tpu.utils import compile_cache
    return compile_cache.enable(), device_summary()


def _child_report(phase, stats, device, **fields):
    import jax
    peaks = {}
    for d in jax.local_devices():
        ms = d.memory_stats()
        if ms is not None:      # the CPU backend reports none
            peaks[str(d.id)] = int(ms["peak_bytes_in_use"])
    snap = stats.snapshot()
    print(json.dumps({
        "phase": phase, "device": device,
        "compile_cache": snap["cache_dir"],
        "compile_s": round(snap["compile_seconds"], 1),
        "cache_hits": snap["cache_hits"],
        "cache_misses": snap["cache_misses"],
        "peak_bytes_in_use": peaks, **fields}), flush=True)


def _agree(name, got, want, tol, results):
    """Largest deviation over the largest reference magnitude <= tol."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape}")
    check(np.isfinite(got).all(), f"{name}: kernel output is not finite")
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-6))
    results[name] = round(err, 5)
    check(err <= tol, f"{name}: deviates {err:.4f} from its reference, "
          f"tolerance {tol}")


def _scattered_tables(rng, kvlen, mb, bs):
    """Block tables of ``mb`` entries for rows of ``kvlen`` cached tokens,
    scattered over a pool of ``nb`` blocks of ``bs`` rows (``nb`` itself is
    the unmapped sentinel), and ``live [nb, bs]``: the pool rows some row
    may read. Returns ``(tables, live, nb)``."""
    import numpy as np
    nb = int(sum(-(-k // bs) for k in kvlen)) + 4
    perm = rng.permutation(nb)
    tables = np.full((len(kvlen), mb), nb, np.int32)
    live = np.zeros((nb, bs), bool)
    used = 0
    for r, k in enumerate(kvlen):
        for b in range(-(-k // bs)):
            tables[r, b] = perm[used]
            live[perm[used], :min(bs, k - b * bs)] = True
            used += 1
    return tables, live, nb


def _poisoned_ragged_case(rng, rows, nh, nkv, hd, *, mb, bs=32, pad=12,
                          vd=None):
    """The ragged kernel's arguments for ``rows`` of (query span, kv length
    after this step; span 0 is a dead row) packed back to back with ``pad``
    rows in no span behind them: bf16, tables of ``mb`` entries scattered
    over a pool that is NaN wherever no live row may read (stale rows of a
    mapped block, unmapped blocks), so a kernel that reads one returns
    NaN. ``vd``: a value's width where it is not a key's."""
    import jax.numpy as jnp
    import numpy as np
    qlen = np.array([q for q, _ in rows], np.int32)
    kvlen = np.array([k for _, k in rows], np.int32)
    tables, live, nb = _scattered_tables(rng, kvlen, mb, bs)
    pool = rng.randn(2, nb, bs, nkv, hd).astype(np.float32)
    pool[:, ~live] = np.nan
    qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).astype(np.int32)
    q = rng.randn(int(qlen.sum()) + pad, nh, hd).astype(np.float32)
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool[0], jnp.bfloat16),
            jnp.asarray(pool[1][..., :vd], jnp.bfloat16), jnp.asarray(tables),
            jnp.asarray(qstart), jnp.asarray(qlen), jnp.asarray(kvlen))


def phase_kernels(rehearse):
    stats, device = _child_start(rehearse)
    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np

    from paddle_tpu.kernels.flash_attention import _ref_attention
    from paddle_tpu.kernels.pallas_flash import flash_attention_pallas
    from paddle_tpu.kernels.pallas_paged_decode import (
        paged_decode_attention_pallas, paged_decode_attention_reference)
    from paddle_tpu.kernels.pallas_ragged_attention import (
        ragged_attention_reference, ragged_paged_attention_pallas)

    size = REHEARSAL if rehearse else FULL
    bf16 = jnp.bfloat16
    errors = {}

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def ragged_agrees(name, args, piece=64, window=None, sink=None):
        # the oracle gathers every token's whole table: row by row, `piece`
        # span tokens a call (tokens lo .. lo + n of a span are a span of n
        # whose kv ends where theirs does), or a cell's step is tens of GB
        q, pk, pv, tables, qstart, qlen, kvlen = args
        want = np.zeros(q.shape[:2] + (pv.shape[-1],), np.float32)
        one = jnp.zeros(1, jnp.int32)
        kernel_kw = dict(window=window, **({} if sink is None
                                           else {"sink": sink}))
        for r, (at, n_r, end) in enumerate(zip(*map(np.asarray, args[4:]))):
            for lo in range(0, n_r, piece):
                n = min(piece, n_r - lo)
                want[at + lo:at + lo + n] = reference(
                    functools.partial(ragged_attention_reference,
                                      **kernel_kw),
                    q[at + lo:at + lo + n], pk,
                    pv, tables[r:r + 1], one, one + n, one + end - n_r + lo + n)
        got = jax.jit(functools.partial(ragged_paged_attention_pallas,
                                        **kernel_kw))(*args)
        _agree(name, got, want, TOL_FWD, errors)
        check(not np.asarray(got[int(np.asarray(qlen).sum()):],
                             np.float32).any(),
              f"{name}: rows outside every span are not exact zeros")

    # ---- the ragged kernel at the serving cells' own steps: a 512-token
    # chunk resumed 3 k into its prompt beside decode rows (Mistral, the
    # general walk in groups of pages), and 24 decode rows over 100-1,300
    # cached tokens (OLMoE, the one-token walk) --------------------------
    for tag, (nh, nkv, hd, mb, rows) in size["ragged_cells"].items():
        ragged_agrees(f"ragged {tag}", _poisoned_ragged_case(
            np.random.RandomState(len(rows)), rows, nh, nkv, hd, mb=mb))
    # ---- ... and at their decode-only steps, whose buffer holds the slots'
    # rows and no more (the unified step's smaller packed size) -----------
    for tag, (nh, nkv, hd, mb, rows, packed) in \
            size["ragged_decode_only"].items():
        live = sum(q for q, _ in rows)
        ragged_agrees(f"ragged {tag}", _poisoned_ragged_case(
            np.random.RandomState(packed), rows, nh, nkv, hd, mb=mb,
            pad=packed - live))

    # ---- ... and under a window (Phi-4-mini-flash's window layers): the
    # walk starts at the group that holds a pair's first visible key ------
    for tag, (nh, nkv, hd, mb, rows, window) in \
            size["ragged_window"].items():
        ragged_agrees(f"ragged {tag}", _poisoned_ragged_case(
            np.random.RandomState(window), rows, nh, nkv, hd, mb=mb),
            window=window)

    # ---- ... and with keys wider than values, under a window with a sink a
    # head and without either (MiMo-V2-Flash's window and full layers) ----
    for tag, (nh, nkv, hd, vd, mb, rows, window, packed) in \
            size["ragged_widths"].items():
        live = sum(q for q, _ in rows)
        rng = np.random.RandomState(packed + nkv)
        # (the oracle's float32 [piece, the table's keys, heads, key width]
        # is 1.6 GB a token at 1,024 entries of 32 x 64 heads x 192: one
        # token a call there; 64 a call, the default, asked for 103 GB)
        ragged_agrees(
            f"ragged {tag}", _poisoned_ragged_case(
                rng, rows, nh, nkv, hd, mb=mb, pad=packed - live, vd=vd),
            piece=max(1, min(64, (1 << 31) // (mb * 32 * nh * hd * 4))),
            window=window, sink=None if window is None else jnp.asarray(
                4.0 + rng.randn(nh), jnp.float32))

    # ---- ... and where every pair's first group of pool pages is fetched
    # while the pair before it still computes, with and without the window:
    # rows of one group and of an odd number of groups in turn ------------
    for tag, (nh, nkv, hd, mb, rows, window) in \
            size["ragged_handover"].items():
        for w in (None, window):
            ragged_agrees(f"ragged {tag} window {w}", _poisoned_ragged_case(
                np.random.RandomState(len(rows)), rows, nh, nkv, hd, mb=mb),
                window=w)

    for nh, nkv, hd in size["geometries"]:
        tag = f"{nh}/{nkv}/{hd}"
        rng = np.random.RandomState(nh * 131 + nkv)

        def normal(*shape):
            return rng.randn(*shape).astype(np.float32)

        # rows: (query span, kv length after this step). Span-1 rows are
        # decode rows whose lengths end at a block start, mid-block and at a
        # block end; one span-n chunk resumes at 37 and ends mid-block at
        # 77; one row is dead.
        rows = [(1, 1), (1, 37), (1, 64), (1, 100), (40, 77), (0, 0)]
        args = _poisoned_ragged_case(rng, rows, nh, nkv, hd, mb=4)
        _, pool_k, pool_v, tables, _, _, kvlen = args
        R, kvlen = len(rows), np.asarray(kvlen)
        ragged_agrees(f"ragged {tag}", args)

        # ---- single-token decode through the same tables -----------------
        dargs = (jnp.asarray(normal(R, nh, hd), bf16), pool_k, pool_v,
                 tables, jnp.asarray(np.maximum(kvlen, 0)))
        _agree(f"paged_decode {tag}",
               jax.jit(paged_decode_attention_pallas)(*dargs),
               reference(paged_decode_attention_reference, *dargs),
               TOL_FWD, errors)

        # ---- flash attention, forward and backward -----------------------
        S = size["flash_seq"]
        fq = jnp.asarray(normal(1, S, nh, hd), bf16)
        fk = jnp.asarray(normal(1, S, nkv, hd), bf16)
        fv = jnp.asarray(normal(1, S, nkv, hd), bf16)
        fw = jnp.asarray(normal(1, S, nh, hd), bf16)     # d(loss)/d(out)

        def both(attn):
            def loss(q_, k_, v_, w_):
                o = attn(q_, k_, v_, True)
                return jnp.sum((o * w_).astype(jnp.float32)), o
            return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

        (_, o), grads = jax.jit(both(flash_attention_pallas))(fq, fk, fv, fw)
        (_, o_ref), grads_ref = reference(both(_ref_attention),
                                          fq, fk, fv, fw)
        _agree(f"flash fwd {tag}", o, o_ref, TOL_FWD, errors)
        for g, g_ref, n in zip(grads, grads_ref, ("dq", "dk", "dv")):
            _agree(f"flash {n} {tag}", g, g_ref, TOL_BWD, errors)

    # ---- the routed FFN (grouped matmuls) against every-expert-masked:
    # whole (OLMoE), then with one chip's share of a wider, group-limited,
    # scaled router (DeepSeek-V2) ----------------------------------------
    from paddle_tpu.kernels.moe_ffn import (_capacity, moe_ffn,
                                            moe_ffn_reference)

    def routed_ffn(tag, seed, hidden, router_width, held, width, rows,
                   stack=0, forced=False, **routing):
        """``stack``: the expert weights as a stack of that many layers, the
        last of them read in place. ``forced``: a router that puts every
        pick on a held expert, so that the held pairs overflow the buffer
        sized for a chip's share (``moe_ffn._capacity``) and take it in
        several passes."""
        rng = np.random.RandomState(seed)
        lead = (stack,) if stack else ()
        weights = [
            jnp.asarray(0.02 * rng.randn(*shape).astype(np.float32), bf16)
            for shape in ((hidden, router_width),
                          lead + (held, hidden, width),
                          lead + (held, hidden, width),
                          lead + (held, width, hidden))]
        if forced:
            weights[0] = jnp.zeros_like(weights[0]).at[
                0, :routing["top_k"]].set(50.0)
        layer = dict(layer=jnp.int32(stack - 1)) if stack else {}
        for n_rows, n_live in rows:
            h = rng.randn(n_rows, hidden).astype(np.float32)
            if forced:
                h[:, 0] = 1.0
            h = jnp.asarray(h, bf16)
            live = np.zeros(n_rows, bool)   # live rows spread over the buffer
            live[np.linspace(0, n_rows - 1, n_live).astype(int)] = True
            margs = (h, *weights, jnp.asarray(live))
            got, stats_got = jax.jit(
                lambda *a: moe_ffn(*a[:5], live=a[5], **layer, **routing))(
                *margs)
            want, stats_want = reference(
                lambda *a: moe_ffn_reference(
                    a[0], a[1], *(w[-1] if stack else w for w in a[2:5]),
                    live=a[5], **routing), *margs)
            name = f"moe_ffn {tag}{n_live}/{n_rows}"
            _agree(name, got, want, TOL_FWD, errors)
            pairs, picks, compact = (int(stats_got[i]) for i in (0, 3, 4))
            cap = _capacity(n_rows * routing["top_k"], held, router_width)
            check(np.array_equal(np.asarray(stats_got),
                                 np.asarray(stats_want))
                  and picks == n_live * routing["top_k"]
                  and (pairs == picks if held == router_width or forced
                       else 0 < pairs < picks)
                  # one pass on the buffer of a chip's share, where there
                  # is one and the held pairs fit it
                  and compact == (cap is not None and pairs <= cap),
                  f"{name}: routing summary {np.asarray(stats_got)} != "
                  f"{np.asarray(stats_want)}")

    H, E, I, K = size["moe"]["widths"]
    routed_ffn("", 64, H, E, E, I, size["moe"]["rows"], top_k=K)
    H, E, held, I, K, groups, top_g = size["moe_share"]["widths"]
    routed_ffn("held ", 160, H, E, held, I, size["moe_share"]["rows"],
               top_k=K, n_group=groups, topk_group=top_g, first_held=0,
               scale=16.0)
    H, E, held, I, K = size["moe_wide"]["widths"]
    routed_ffn("64 of 512 ", 512, H, E, held, I, size["moe_wide"]["rows"],
               top_k=K, renormalize=True, first_held=0)
    # ... as a layer of a stack, on the buffer of the pairs a chip's share
    # takes: in one pass and, every pick forced onto the share, in four
    for forced in (False, True):
        routed_ffn("64 of 512 in a stack " + "forced " * forced, 513, H, E,
                   held, I, size["moe_wide"]["rows"], stack=2, forced=forced,
                   top_k=K, renormalize=True, first_held=0)
    # ... and at a whole-prompt program's rows, where the buffer is far over
    # ``PRODUCT_SLOTS`` and the way back is the gather by pair, as at the
    # 640 rows above and not at the 128 (the rehearsal's sizes reach it by
    # lowering the bar)
    from paddle_tpu.kernels import moe_ffn as moe_ffn_module
    bar = moe_ffn_module.PRODUCT_SLOTS
    if rehearse:
        moe_ffn_module.PRODUCT_SLOTS = 0
    rows = size["moe_wide"]["whole_prompt"]
    check(all(_capacity(n * K, held, E) > moe_ffn_module.PRODUCT_SLOTS
              for n, _ in rows), "moe_ffn whole-prompt: the buffer is under "
          "PRODUCT_SLOTS, so the gather by pair did not run")
    for forced in (False, True):
        routed_ffn("64 of 512 whole-prompt " + "forced " * forced, 514, H, E,
                   held, I, rows, stack=2, forced=forced, top_k=K,
                   renormalize=True, first_held=0)
    moe_ffn_module.PRODUCT_SLOTS = bar

    # ---- latent attention, absorbed kernel against expanded oracle ------
    from paddle_tpu.kernels.pallas_mla_ragged_attention import (
        PAGES, SLOTS, latent_row_width, mla_ragged_attention_pallas,
        mla_ragged_attention_reference)
    nh, rank, nope, rope, vd = size["mla"]["widths"]
    scale = (nope + rope) ** -0.5

    def absorbed(q_nope, q_pe, w_kvb, *span):
        w = w_kvb.reshape(rank, nh, nope + vd)
        o_lat = mla_ragged_attention_pallas(
            jnp.einsum("thd,rhd->thr", q_nope, w[..., :nope]), q_pe, *span,
            scale=scale)
        return jnp.einsum("thr,rhd->thd", o_lat, w[..., nope:])

    def latent_agrees(name, rows, mb, pad, bs=32):
        """``rows`` of (query span, kv length after this step) packed back
        to back with ``pad`` rows in no span behind them, over a latent pool
        that is NaN wherever no live row may read; the expanded oracle runs
        span by span (every token's whole table at once is tens of GB at
        the cell's lengths)."""
        rng = np.random.RandomState(512 + len(rows))
        qlen = np.array([q for q, _ in rows], np.int32)
        kvlen = np.array([k for _, k in rows], np.int32)
        tables, live, nb = _scattered_tables(rng, kvlen, mb, bs)
        pool = rng.randn(1, nb, bs, latent_row_width(rank, rope)).astype(
            np.float32)
        pool[..., rank + rope:] = 0.0
        pool[0][~live] = np.nan
        qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).astype(np.int32)
        T = int(qlen.sum()) + pad
        q_nope = jnp.asarray(rng.randn(T, nh, nope).astype(np.float32), bf16)
        q_pe = jnp.asarray(rng.randn(T, nh, rope).astype(np.float32), bf16)
        w_kvb = jnp.asarray(
            rng.randn(rank, nh * (nope + vd)).astype(np.float32)
            * rank ** -0.5, bf16)
        pool, tables = jnp.asarray(pool, bf16), jnp.asarray(tables)
        got = jax.jit(absorbed)(
            q_nope, q_pe, w_kvb, pool, tables, jnp.asarray(qstart),
            jnp.asarray(qlen), jnp.asarray(kvlen))
        want = np.zeros(got.shape, np.float32)
        one = jnp.zeros(1, jnp.int32)
        for r, (at, n, end) in enumerate(zip(qstart, qlen, kvlen)):
            if n:
                want[at:at + n] = reference(
                    lambda *a: mla_ragged_attention_reference(
                        *a, scale=scale),
                    q_nope[at:at + n], q_pe[at:at + n], w_kvb, pool,
                    tables[r:r + 1], one, one + int(n), one + int(end))
        _agree(name, got, want, TOL_FWD, errors)
        check(not np.asarray(got[int(qlen.sum()):], np.float32).any(),
              f"{name}: rows outside every span are not exact zeros")

    # decode rows ending at a block start, mid-block and after more than one
    # group of pages; a chunk with a cached prefix; a dead row
    latent_agrees("mla_ragged", [(1, 1), (1, 37), (1, 64), (1, 700),
                                 (40, 77), (0, 0)], mb=24, pad=12)
    mb, rows = size["mla"]["decode_only"]
    latent_agrees(f"mla_ragged {len(rows)} rows", rows, mb=mb, pad=0)
    # decode rows of as many groups of 16 pool pages as lie around the edges
    # of the walk's pipeline (``_walk_ahead``): fewer than a pair starts
    # ahead, as many, one and two more, several rounds of the slots. A copy
    # left unwaited or a slot read early shows only on the chip, as NaN
    edges = sorted({1, max(SLOTS - 2, 1), SLOTS - 1, SLOTS, SLOTS + 1,
                    2 * SLOTS + 1})
    group = PAGES * 32
    latent_agrees("mla_ragged pipeline edges",
                  [(1, group * n - 45 - 7 * i) for i, n in enumerate(edges)]
                  + [(1, group * SLOTS)], mb=PAGES * edges[-1], pad=0)

    # ---- sparse attention over the latent pool: index scores, the
    # selection and the attention over it against their oracles -----------
    from paddle_tpu.kernels import dsa
    nh, rank, nope, rope, vd, hi, hd_i, topk = size["dsa"]["widths"]
    scale = (nope + rope) ** -0.5

    def sparse_agrees(name, rows, mb, bs=32):
        """``rows`` as ``latent_agrees``, over a latent pool and an
        index-key pool that are NaN wherever no live row may read. The
        selection is judged as a SET on the kernel's own scores; the
        attention on that set, the expanded oracle a few queries at a
        time."""
        rng = np.random.RandomState(43 + len(rows))
        qlen = np.array([q for q, _ in rows], np.int32)
        kvlen = np.array([k for _, k in rows], np.int32)
        tables, live, nb = _scattered_tables(rng, kvlen, mb, bs)
        pool = rng.randn(1, nb, bs, latent_row_width(rank, rope)).astype(
            np.float32)
        pool[..., rank + rope:] = 0.0
        ipool = rng.randn(1, nb, bs, hd_i).astype(np.float32)
        pool[0][~live] = np.nan
        ipool[0][~live] = np.nan
        qstart = np.concatenate([[0], np.cumsum(qlen)[:-1]]).astype(np.int32)
        T = int(qlen.sum())
        q_nope = jnp.asarray(rng.randn(T, nh, nope).astype(np.float32), bf16)
        q_pe = jnp.asarray(rng.randn(T, nh, rope).astype(np.float32), bf16)
        w_kvb = jnp.asarray(
            rng.randn(rank, nh * (nope + vd)).astype(np.float32)
            * rank ** -0.5, bf16)
        q_i = jnp.asarray(rng.randn(T, hi, hd_i).astype(np.float32), bf16)
        w_i = jnp.asarray(rng.randn(T, hi).astype(np.float32))
        pool, ipool = jnp.asarray(pool, bf16), jnp.asarray(ipool, bf16)
        span = tuple(jnp.asarray(x) for x in (tables, qstart, qlen, kvlen))
        scores = jax.jit(dsa.dsa_index_scores_pallas)(q_i, w_i, ipool, *span)
        want = reference(dsa.dsa_index_scores_reference, q_i, w_i, ipool,
                         *span)
        seen = np.asarray(want) > 0.5 * dsa.NEG_INF
        check(((np.asarray(scores) > 0.5 * dsa.NEG_INF) == seen).all(),
              f"{name}: the kernel scores other keys than the oracle")
        _agree(f"dsa_index_scores {name}", np.where(seen, scores, 0.0),
               np.where(seen, want, 0.0), TOL_FWD, errors)
        mask = jax.jit(lambda s: dsa.dsa_select(s, topk))(scores)
        check(bool((mask == jax.jit(lambda s: dsa.dsa_select_reference(
            s, topk))(scores)).all()),
            f"{name}: the selection is not top_k's set")
        check((np.asarray(mask).sum(-1) == np.minimum(
            seen.sum(-1), topk)).all(), f"{name}: a set of the wrong size")

        def absorbed(q_nope, q_pe, w_kvb, pool, mask, *span):
            w = w_kvb.reshape(rank, nh, nope + vd)
            o_lat = dsa.dsa_attention_pallas(
                jnp.einsum("thd,rhd->thr", q_nope, w[..., :nope]), q_pe,
                pool, *span, dsa.selection_bias(
                    mask, nh, table_entries=mb, block_size=bs), scale=scale)
            return jnp.einsum("thr,rhd->thd", o_lat, w[..., nope:])

        got = jax.jit(absorbed)(q_nope, q_pe, w_kvb, pool, mask, *span)
        want = np.zeros(got.shape, np.float32)
        one = jnp.zeros(1, jnp.int32)
        for r, (at, n, end) in enumerate(zip(qstart, qlen, kvlen)):
            for i in range(0, int(n), 4):       # four queries' rows a call
                m = min(4, int(n) - i)
                want[at + i:at + i + m] = reference(
                    lambda *a: dsa.dsa_attention_reference(
                        *a, scale=scale, k=topk),
                    q_nope[at + i:at + i + m], q_pe[at + i:at + i + m],
                    w_kvb, pool, span[0][r:r + 1], one, one + m,
                    one + int(end) - int(n) + i + m,
                    mask[at + i:at + i + m])
        _agree(f"dsa_attention {name}", got, want, TOL_FWD, errors)

    for name, rows in size["dsa"]["rows"].items():
        sparse_agrees(name, rows, size["dsa"]["mb"])

    # ---- the gated delta rule: both kernels against the recurrence ------
    from paddle_tpu.kernels import gated_delta_rule as gdr
    rng = np.random.RandomState(33)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32))

    def delta_rule_agrees(tag, nh, dk, dv, nk, R, T):
        """Both kernels at ``nh`` value heads on ``nk`` key heads."""
        q = gdr.l2norm(rand(T, nk, dk), dk ** -0.5)
        k = gdr.l2norm(rand(T, nk, dk))
        v = rand(T, nh, dv)
        g = -1.6 * jnp.asarray(rng.rand(T, nh).astype(np.float32))
        beta = 2.0 * jnp.asarray(rng.rand(T, nh).astype(np.float32))
        store = rand(2, R, *gdr.state_shape(nh, dk, dv))
        # a decode step: every slot but two has a row, one starts a sequence
        live = np.ones(R, bool)
        live[[1, R - 1]] = False
        fresh = np.zeros(R, bool)
        fresh[2] = True
        got = jax.jit(lambda *a: gdr.gdn_recurrent_update(
            *a, layer=1, live=live, fresh=fresh))(
                q[:R], k[:R], v[:R], g[:R], beta[:R], store)
        want = reference(lambda *a: gdr.gdn_reference(
            *a, layer=1, seg=np.where(live, np.arange(R), R), first=fresh),
            q[:R], k[:R], v[:R], g[:R], beta[:R], store)
        _agree(f"gdn_recurrent_update {tag}o", np.asarray(got[0])[live],
               np.asarray(want[0])[live], TOL_GDN, errors)
        _agree(f"gdn_recurrent_update {tag}state", got[1], want[1], TOL_GDN, errors)
        # the decode-only step: a buffer of the slots' rows alone. Every slot has
        # a row, the fresh one over a stored state that is NaN (it starts from
        # zero whatever its slot held), and the chunk scan, which finds no span
        # over one token there, hands the store back as it is
        live = np.ones(R, bool)
        poisoned = store.at[:, 2].set(jnp.nan)
        got = jax.jit(lambda *a: gdr.gdn_recurrent_update(
            *a, layer=1, live=live, fresh=fresh))(
                q[:R], k[:R], v[:R], g[:R], beta[:R], poisoned)
        want = reference(lambda *a: gdr.gdn_reference(
            *a, layer=1, seg=np.arange(R), first=fresh),
            q[:R], k[:R], v[:R], g[:R], beta[:R], store)
        _agree(f"gdn_recurrent_update {tag}{R} rows o", got[0], want[0], TOL_GDN,
               errors)
        _agree(f"gdn_recurrent_update {tag}{R} rows state", got[1][1], want[1][1],
               TOL_GDN, errors)
        idle = jax.jit(lambda *a: gdr.gdn_chunk_scan(
            *a, layer=0, start=np.arange(R, dtype=np.int32),
            length=np.zeros(R, np.int32), fresh=fresh))(
                q[:R], k[:R], v[:R], g[:R], beta[:R], store)
        check(np.array_equal(np.asarray(idle[1]), np.asarray(store)),
              f"gdn_chunk_scan {tag}{R} rows, no span: the store changed")
        # a chunk step: decode rows first (not the scan's), then a chunk that
        # continues its slot's state and a fresh one that starts in the block
        # where the first ends
        cut = 5 + (T - 5) * 3 // 5
        start, length = np.zeros(R, np.int32), np.zeros(R, np.int32)
        start[3], length[3] = 5, cut - 5
        start[0], length[0] = cut, T - cut - 3
        fresh = np.zeros(R, bool)
        fresh[0] = True
        seg = np.full(T, R, np.int32)
        seg[5:cut], seg[cut:T - 3] = 3, 0
        first = np.zeros(T, bool)
        first[cut] = True
        got = jax.jit(lambda *a: gdr.gdn_chunk_scan(
            *a, layer=0, start=start, length=length, fresh=fresh))(
                q, k, v, g, beta, store)
        want = reference(lambda *a: gdr.gdn_reference(
            *a, layer=0, seg=seg, first=first), q, k, v, g, beta, store)
        _agree(f"gdn_chunk_scan {tag}o", np.asarray(got[0])[5:T - 3],
               np.asarray(want[0])[5:T - 3], TOL_GDN, errors)
        _agree(f"gdn_chunk_scan {tag}state", got[1], want[1], TOL_GDN, errors)

    delta_rule_agrees("", *size["gdn"]["widths"], size["gdn"]["widths"][0],
                      size["gdn"]["slots"], size["gdn"]["packed"])
    # Qwen3-Next's linear layers: value head h on key head h // 2
    delta_rule_agrees("grouped ", *size["gdn_grouped"]["widths"],
                      size["gdn_grouped"]["slots"],
                      size["gdn_grouped"]["packed"])

    # ---- the selective scan: both kernels against the recurrence --------
    from paddle_tpu.kernels import selective_scan as ssk
    C, N = size["ssm"]["widths"]
    R, T = size["ssm"]["slots"], size["ssm"]["packed"]
    rng = np.random.RandomState(37)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (T, C))
                            ).astype(np.float32))
    u = dt * rand(T, C)
    bm, cm = rand(T, N), rand(T, N)
    a = -jnp.broadcast_to(jnp.arange(1.0, N + 1)[:, None], (N, C))
    store = rand(2, R, N, C)
    # the decode-only step: every slot has a row, one starts a sequence over
    # a stored state that is NaN; the chunk scan finds no span and hands the
    # store back as it is
    live = np.ones(R, bool)
    fresh = np.zeros(R, bool)
    fresh[2] = True
    got = jax.jit(lambda *x: ssk.ssm_recurrent_update(
        *x, layer=1, live=live, fresh=fresh))(
            dt[:R], u[:R], bm[:R], cm[:R], a, store.at[:, 2].set(jnp.nan))
    want = reference(lambda *x: ssk.ssm_reference(
        *x, layer=1, seg=np.arange(R), first=fresh),
        dt[:R], u[:R], bm[:R], cm[:R], a, store)
    _agree(f"ssm_recurrent_update {R} rows y", got[0], want[0], TOL_GDN,
           errors)
    _agree(f"ssm_recurrent_update {R} rows state", got[1][1], want[1][1],
           TOL_GDN, errors)
    idle = jax.jit(lambda *x: ssk.ssm_chunk_scan(
        *x, layer=0, start=np.arange(R, dtype=np.int32),
        length=np.zeros(R, np.int32), fresh=fresh, min_span=2))(
            dt[:R], u[:R], bm[:R], cm[:R], a, store)
    check(np.array_equal(np.asarray(idle[1]), np.asarray(store)),
          f"ssm_chunk_scan {R} rows, no span: the store changed")
    # a chunk step: decode rows first (not the scan's), then a chunk that
    # continues its slot's state and a fresh one that starts in the block
    # where the first ends
    cut = 5 + (T - 5) * 3 // 5
    start, length = np.zeros(R, np.int32), np.zeros(R, np.int32)
    start[3], length[3] = 5, cut - 5
    start[0], length[0] = cut, T - cut - 3
    fresh = np.zeros(R, bool)
    fresh[0] = True
    seg = np.full(T, R, np.int32)
    seg[5:cut], seg[cut:T - 3] = 3, 0
    first = np.zeros(T, bool)
    first[cut] = True
    got = jax.jit(lambda *x: ssk.ssm_chunk_scan(
        *x, layer=0, start=start, length=length, fresh=fresh, min_span=2))(
            dt, u, bm, cm, a, store)
    want = reference(lambda *x: ssk.ssm_reference(
        *x, layer=0, seg=seg, first=first), dt, u, bm, cm, a, store)
    _agree("ssm_chunk_scan y", np.asarray(got[0])[5:T - 3],
           np.asarray(want[0])[5:T - 3], TOL_GDN, errors)
    _agree("ssm_chunk_scan state", got[1], want[1], TOL_GDN, errors)

    # ---- the Mamba-2 recurrence: both kernels against the recurrence ----
    from paddle_tpu.kernels import ssd as ssdk
    H, P, G, N = size["ssd"]["widths"]
    R, T = size["ssd"]["slots"], size["ssd"]["packed"]
    rng = np.random.RandomState(41)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (T, H))
                            ).astype(np.float32))
    xh, bm, cm = rand(T, H, P), rand(T, G, N), rand(T, G, N)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)).astype(np.float32))
    store = rand(2, R, *ssdk.state_shape(H, P, G, N))
    # the decode-only step: every slot has a row, one starts a sequence over
    # a stored state that is NaN
    live = np.ones(R, bool)
    fresh = np.zeros(R, bool)
    fresh[2] = True
    got = jax.jit(lambda *x: ssdk.ssd_recurrent_update(
        *x, layer=1, live=live, fresh=fresh))(
            xh[:R], dt[:R], a, bm[:R], cm[:R], store.at[:, 2].set(jnp.nan))
    want = reference(lambda *x: ssdk.ssd_reference(
        *x, layer=1, seg=np.arange(R), first=fresh),
        xh[:R], dt[:R], a, bm[:R], cm[:R], store)
    _agree(f"ssd_recurrent_update {R} rows y", got[0], want[0], TOL_GDN,
           errors)
    _agree(f"ssd_recurrent_update {R} rows state", got[1][1], want[1][1],
           TOL_GDN, errors)
    # a chunk step: decode rows first (not the scan's), then a chunk that
    # continues its slot's state and a fresh one that starts in the block
    # where the first ends
    cut = 5 + (T - 5) * 3 // 5
    start, length = np.zeros(R, np.int32), np.zeros(R, np.int32)
    start[3], length[3] = 5, cut - 5
    start[0], length[0] = cut, T - cut - 3
    fresh = np.zeros(R, bool)
    fresh[0] = True
    seg = np.full(T, R, np.int32)
    seg[5:cut], seg[cut:T - 3] = 3, 0
    first = np.zeros(T, bool)
    first[cut] = True
    got = jax.jit(lambda *x: ssdk.ssd_chunk_scan(
        *x, layer=0, start=start, length=length, fresh=fresh))(
            xh, dt, a, bm, cm, store)
    want = reference(lambda *x: ssdk.ssd_reference(
        *x, layer=0, seg=seg, first=first), xh, dt, a, bm, cm, store)
    _agree("ssd_chunk_scan y", np.asarray(got[0])[5:T - 3],
           np.asarray(want[0])[5:T - 3], TOL_GDN, errors)
    _agree("ssd_chunk_scan state", got[1], want[1], TOL_GDN, errors)

    import importlib.metadata as md
    _child_report(
        "kernels", stats, device, max_error=errors,
        tolerance={"forward": TOL_FWD, "backward": TOL_BWD,
                   "delta_rule": TOL_GDN},
        versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "libtpu": md.version("libtpu"),
                  "python": sys.version.split()[0]})
    return 0


def phase_train(rehearse, four_chips, ref_loss):
    stats, device = _child_start(rehearse)
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import (LlamaForCausalLM, llama_7b,
                                         llama_tiny)
    from paddle_tpu.optimizer import AdamW

    size = (REHEARSAL if rehearse else FULL)["train"]
    make = llama_tiny if rehearse else llama_7b
    cfg = make(num_hidden_layers=size["layers"], dtype="bfloat16",
               loss_chunk=size["seq"] // 4,
               max_position_embeddings=size["seq"])
    mesh, stage = None, 0
    if four_chips:
        from paddle_tpu.distributed import fleet
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {"sharding_degree": 2, "mp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        mesh, stage = fleet.get_hybrid_communicate_group().mesh, 2
        check(dict(mesh.shape)["sharding"] == 2
              and dict(mesh.shape)["mp"] == 2, f"mesh is {dict(mesh.shape)}")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=3e-4, parameters=model.parameters())
    step = TrainStep(model, lambda loss, _lab: loss, opt, mesh=mesh,
                     sharding_stage=stage)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (size["batch"], size["seq"])).astype(np.int32))

    t0 = time.perf_counter()
    compiled = step.compile_step((ids, ids), (ids,))
    aot_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    mosaic_calls = compiled.as_text().count("tpu_custom_call")
    if not rehearse:
        # otherwise the jnp branch of flash_attention.attention ran
        check(mosaic_calls > 0, "train step holds no Mosaic custom call")

    losses, step_s = [], []
    for _ in range(size["steps"]):      # one fixed batch: the loss must fall
        t0 = time.perf_counter()
        loss = step.step((ids, ids), (ids,))
        loss.value.block_until_ready()
        step_s.append(round(time.perf_counter() - t0, 3))
        losses.append(float(loss.value))
    check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if ref_loss is not None:
        check(abs(losses[0] - ref_loss) <= TOL_LOSS_4CHIP,
              f"step-0 loss {losses[0]} on four chips, {ref_loss} on one: "
              f"apart by more than {TOL_LOSS_4CHIP}")
    if four_chips and not rehearse:
        # code that has only met a virtual CPU mesh may put all on chip 0
        in_use = {d.id: d.memory_stats()["bytes_in_use"]
                  for d in jax.local_devices()}
        check(len(in_use) >= 4 and all(v > 0 for v in in_use.values()),
              f"memory in use per device: {in_use}")
    _child_report(
        "train4" if four_chips else "train", stats, device,
        mesh=None if mesh is None else dict(mesh.shape),
        losses=[round(x, 5) for x in losses], ref_loss=ref_loss,
        aot_compile_s=round(aot_s, 1), step_s=step_s,
        mosaic_custom_calls=mosaic_calls,
        step_program={"argument_bytes": int(mem.argument_size_in_bytes),
                      "output_bytes": int(mem.output_size_in_bytes),
                      "alias_bytes": int(mem.alias_size_in_bytes),
                      "temp_bytes": int(mem.temp_size_in_bytes)})
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="debug this script on the CPU backend at a tiny "
                         "size (never a chip result)")
    ap.add_argument("--phase", choices=("kernels", "train", "train4"),
                    help="internal: run one phase in this process")
    ap.add_argument("--ref-loss", type=float, default=None,
                    help="internal: the one-chip step-0 loss for train4")
    args = ap.parse_args()
    if args.phase == "kernels":
        return phase_kernels(args.rehearse_cpu)
    if args.phase in ("train", "train4"):
        return phase_train(args.rehearse_cpu, args.phase == "train4",
                           args.ref_loss)
    try:
        return parent(args.rehearse_cpu)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
