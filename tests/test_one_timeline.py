"""One timeline (ISSUE 24): the span tracer's begin / end form and its
``annotate`` mirror, the ``launch`` span's two children and the gateway's
``loop`` span, the clock's stated origin, what each step asked of the ragged
kernel (``ragged_grid_counts``, the ``dispatch`` span's args,
``serving_step_tokens_total``), the names on the kernels and the training
phases, and ``GET /debug/xplane``.
"""
import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental import pallas as pl
from paddle_tpu.kernels.pallas_ragged_attention import (_one_token_walk,
                                                        _plane_rows,
                                                        _query_block,
                                                        _row_chunks,
                                                        grid_params,
                                                        pages_per_update,
                                                        query_block_rows,
                                                        ragged_grid_counts)
from paddle_tpu.profiler import chrometrace
from paddle_tpu.profiler.tracing import (NULL_SPAN, TID_ENGINE, TID_GATEWAY,
                                         TID_REQ0, SpanTracer)
from paddle_tpu.serving import GenerationRequest, VirtualClock
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving.decode import attention_grid
from paddle_tpu.serving.server import serve

import serving_support
from test_metrics_prom import parse_prometheus
# test_tracing's engines (256 positions, a tracer hung on), its model and so
# its programs
from test_tracing import NUM_SLOTS, S_MAX, _engine


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=31)


def _reqs():
    long = np.arange(1, 81, dtype=np.int32)        # chunked: 32 + 32 + 16
    return [GenerationRequest(prompt=long, max_new_tokens=4),
            GenerationRequest(prompt=[5, 6, 7, 8], max_new_tokens=6)]


class Mirror:
    """An ``annotate`` factory that records what it was asked to open."""

    def __init__(self):
        self.opened, self.closed = [], []

    def __call__(self, name, **args):
        mirror = self

        class _Ann:
            def __enter__(self):
                mirror.opened.append((name, args))
                return self

            def __exit__(self, *exc):
                mirror.closed.append(name)
                return False
        return _Ann()


class CountingClock:
    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return self.reads * 1e-3


# ------------------------------------------------------------- the tracer
class TestSpanForm:
    def test_end_merges_args_and_mirror_sees_the_start_args(self):
        mirror = Mirror()
        tr = SpanTracer(clock=VirtualClock(), annotate=mirror).enable()
        sp = tr.span("step", args={"step": 7})
        sp.end({"tokens": 3})
        with tr.span("loop", tid=TID_GATEWAY):
            pass
        evs = tr.events()
        assert evs[0]["args"] == {"step": 7, "tokens": 3}
        assert list(evs[0]["args"]) == ["step", "tokens"]
        assert "args" not in evs[1] and evs[1]["tid"] == TID_GATEWAY
        assert mirror.opened == [("step", {"step": 7}), ("loop", {})]
        assert mirror.closed == ["step", "loop"]

    def test_request_lanes_are_not_mirrored(self):
        mirror = Mirror()
        tr = SpanTracer(clock=VirtualClock(), annotate=mirror).enable()
        tr.span("decode", tid=TID_REQ0).end()
        tr.complete("queued", None, tid=TID_REQ0 + 1)
        assert mirror.opened == [] and len(tr.events()) == 2

    def test_disabled_opens_nothing_and_reads_no_clock(self):
        mirror, clock = Mirror(), CountingClock()
        tr = SpanTracer(clock=clock, annotate=mirror)
        sp = tr.span("step", args={"step": 0})
        assert sp is NULL_SPAN
        sp.end({"tokens": 1})
        assert mirror.opened == [] and clock.reads == 0
        assert tr.events() == []

    def test_mirror_closes_when_the_tracer_stopped_meanwhile(self):
        mirror = Mirror()
        tr = SpanTracer(clock=VirtualClock(), annotate=mirror).enable()
        sp = tr.span("loop", tid=TID_GATEWAY)
        tr.disable()
        sp.end()
        assert mirror.closed == ["loop"] and tr.events() == []

    def test_clock_origin_is_stated(self):
        tr = SpanTracer().enable()            # a real clock
        origin = tr.export()["otherData"]["clock"]
        assert abs(origin["epoch_unix_ns"] - time.time_ns()) < 60e9
        assert abs(origin["epoch_s"] - time.perf_counter()) < 60
        vc = VirtualClock(start=5.0)
        origin = SpanTracer(clock=vc).enable().export()["otherData"]["clock"]
        assert origin == {"epoch_s": 5.0}     # no wall clock in a replay


# ----------------------------------------------------- engine and gateway
class TestEngineSpans:
    def _run(self, model, annotate, clock=VirtualClock):
        tr = SpanTracer(clock=clock(), annotate=annotate).enable()
        eng = _engine(model, tracer=tr, prefill_chunk=32,
                      prefix_block_size=8)
        outs = eng.generate(_reqs())
        return eng, tr, [o.tolist() for o in outs]

    def test_mirror_gets_every_engine_span_once_and_bytes_do_not_move(self, model):
        _, plain, toks0 = self._run(model, None)
        mirror = Mirror()
        eng, tr, toks1 = self._run(model, mirror)
        assert toks0 == toks1
        # the same bytes with and without a factory (VirtualClock replay)
        assert json.dumps(plain.export(), sort_keys=True) \
            == json.dumps(tr.export(), sort_keys=True)
        lane = [e for e in tr.events()
                if e["ph"] == "X" and e["tid"] == TID_ENGINE]
        assert sorted(n for n, _ in mirror.opened) \
            == sorted(e["name"] for e in lane)
        assert sorted(mirror.closed) == sorted(n for n, _ in mirror.opened)
        steps = [a["step"] for n, a in mirror.opened if n == "step"]
        assert steps == list(range(eng.stats["steps"]))

    def test_disabled_engine_touches_neither_mirror_nor_clock(self, model):
        mirror, clock = Mirror(), CountingClock()
        tr = SpanTracer(clock=clock, annotate=mirror)       # never enabled
        eng = _engine(model, tracer=tr, prefill_chunk=32,
                      prefix_block_size=8)
        eng.generate(_reqs())
        assert mirror.opened == [] and clock.reads == 0

    def test_launch_holds_dispatch_and_device_wait(self, model):
        eng, tr, _ = self._run(model, None,
                               clock=lambda: time.perf_counter)
        evs = [e for e in tr.events() if e["ph"] == "X"]
        launches = [e for e in evs if e["name"] == "launch"]
        assert launches

        def inside(parent, names):
            lo, hi = parent["ts"], parent["ts"] + parent["dur"]
            return [e["name"] for e in evs if e["name"] in names
                    and lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1e-6]
        # a launch dispatches this step's program, then fences the
        # previous step's: the first has nothing to fence, the last
        # (a drain) nothing to dispatch
        kids = [inside(p, ("dispatch", "device-wait")) for p in launches]
        assert kids[0] == ["dispatch"] and kids[-1] == ["device-wait"]
        assert all(k == ["dispatch", "device-wait"] for k in kids[1:-1])
        # every step holds at most one of each, inside it; launch holds
        # dispatch and device-wait as before, host-accept follows it
        leaves = ("sweep", "plan", "launch", "dispatch", "call",
                  "device-wait", "host-accept", "retire")
        steps = [e for e in evs if e["name"] == "step"]
        assert len(steps) == eng.stats["steps"]
        held = [inside(s, leaves) for s in steps]
        assert sum(map(len, held)) == sum(
            1 for e in evs if e["name"] in leaves)      # none outside
        for names in held:
            assert len(set(names)) == len(names)
            order = [n for n in ("sweep", "plan", "launch", "host-accept",
                                 "retire") if n in names]
            assert [n for n in names if n in order] == order
            # every step starts in ``sweep`` and ends in ``retire``, which
            # closes at the reading that closes ``step``
            assert names[0] == "sweep" and names[-1] == "retire"
        for s, r in zip(steps, (e for e in evs if e["name"] == "retire")):
            assert r["ts"] + r["dur"] == pytest.approx(s["ts"] + s["dur"],
                                                       abs=1e-6)
        # ``call`` is the jitted call alone, inside ``dispatch``; what of
        # ``dispatch`` is not under it is the commit
        calls = [e for e in evs if e["name"] == "call"]
        dispatches = [e for e in evs if e["name"] == "dispatch"]
        assert len(calls) == len(dispatches)
        assert all(inside(d, ("call",)) == ["call"] for d in dispatches)
        sweeps = [e["args"] for e in evs if e["name"] == "sweep"]
        assert all(set(a) == {"queued", "admitted"} for a in sweeps)
        assert sweeps[0] == {"queued": 2, "admitted": 2}
        # dispatch says whether it went behind a program in flight; ahead
        # plus the dispatches into an emptied pipeline are all of them
        ahead = [e["args"]["ahead"] for e in evs if e["name"] == "dispatch"]
        assert ahead[0] == 0 and set(ahead) == {0, 1}
        drains = sum(v for k, v in eng.stats.items()
                     if k.startswith("drains_"))
        assert sum(ahead) == eng.stats["steps_dispatched_ahead"]
        assert sum(ahead) + drains == len(ahead) \
            == eng.stats["unified_steps"]
        rows = {(r["lane"], r["name"]): r
                for r in chrometrace.span_self_times(tr.events())}
        lane = chrometrace.lane_name(TID_ENGINE)
        # self times balance: a parent's total is its own time plus its
        # children's, all the way up to the step
        kids = rows[lane, "dispatch"]["total_ms"] \
            + rows[lane, "device-wait"]["total_ms"]
        assert rows[lane, "launch"]["self_ms"] == pytest.approx(
            rows[lane, "launch"]["total_ms"] - kids, abs=2e-3)
        names = ("step", "sweep", "admit", "plan", "launch", "dispatch",
                 "call", "device-wait", "host-accept", "retire", "donate",
                 "prefill_launch")
        assert sum(rows[lane, n]["self_ms"] for n in names
                   if (lane, n) in rows) == pytest.approx(
            rows[lane, "step"]["total_ms"], abs=2e-2)

    def test_dispatch_args_say_what_the_step_asked(self, model):
        eng, tr, _ = self._run(model, None)
        evs = tr.events()
        disp = [e["args"] for e in evs if e["name"] == "dispatch"]
        steps = [e["args"] for e in evs if e["name"] == "step"]
        assert len(disp) == eng.stats["unified_steps"] > 0
        heads = model.config.num_attention_heads
        T = eng._token_budget
        hd = model.config.head_dim
        grid = attention_grid(eng._params, eng.cache.pool.k,
                              eng.cache.max_blocks, heads, T, head_dim=hd)
        # the packed size follows the plan: the token budget where the step
        # carries a chunk, the slots' rows (a whole 8) where it carries none
        assert {a["packed_rows"] for a in disp} == {T, 8} \
            == set(eng.step_rows)
        for a in disp:
            assert a["packed_rows"] == (T if a["prefill_tokens"] else 8)
            # the work list's entries (one query block at either size),
            # plus the KV blocks the loops walk
            tiling = attention_grid(
                eng._params, eng.cache.pool.k, eng.cache.max_blocks, heads,
                a["packed_rows"], head_dim=hd)
            nq = -(-(a["packed_rows"] * heads) // tiling["block_q"])
            assert nq == 1
            assert a["grid_steps"] == nq + NUM_SLOTS + a["live_steps"]
            assert 0 < a["live_steps"] <= (nq + NUM_SLOTS) \
                * eng.cache.max_blocks
            assert a["attn_pairs"] >= a["kv_tokens"] > 0
            # an update a group of pages, of which the engine knows as many
            # as the kernel derives; a decode row takes the one-token walk
            # at either size (2 rows of each KV head's plane a token, the
            # block whole 16-row tiles)
            assert -(-a["live_steps"] // grid["pages"]) \
                <= a["update_steps"] <= a["live_steps"]
            assert tiling["one_token"]
            assert a["one_token_rows"] == a["decode_rows"]
        # 256 keys an update in blocks of 8: the whole table of 32 entries;
        # the packed buffer's 34 tokens are under one block of 128
        assert grid == dict(block_q=T * heads, pages=eng.cache.max_blocks,
                            one_token=True)
        # every token a step span counts is a prefill or a decode token
        assert sum(a["prefill_tokens"] + a["decode_tokens"] for a in disp) \
            == sum(s["tokens"] for s in steps)
        assert sum(a["prefill_tokens"] for a in disp) == 80
        assert eng.stats["step_prefill_tokens"] == 80
        assert eng.stats["step_decode_tokens"] \
            == sum(a["decode_tokens"] for a in disp)


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_dispatch_counts_at_the_tiling_the_kernel_was_built_with(
        kind, monkeypatch):
    """The ``dispatch`` span's counts are an enumeration at the query block
    and the pages an update that the step's ``pallas_call`` was really built
    with (read off the call itself, not derived a second time): the engine
    and the kernel share one ``grid_params``."""
    if kind == "dense":
        model = serving_support.model("llama", seed=31)     # "pallas"
        name = "ragged_paged_attention"
    else:
        model = serving_support.model("deepseek_v2", seed=31)
        name = "mla_ragged_attention"
    built, asked = [], []
    real_call, real_counts = pl.pallas_call, engine_mod.ragged_grid_counts

    def pallas_call(kernel, *a, **kw):
        if kw.get("name") == name:
            built.append((kernel.keywords["tq"], kernel.keywords["pages"]))
        return real_call(kernel, *a, **kw)

    def counts(qstart, qlen, kvlen, **kw):
        asked.append(([int(x) for x in qstart], [int(x) for x in qlen],
                      [int(x) for x in kvlen], kw, len(built)))
        return real_counts(qstart, qlen, kvlen, **kw)

    monkeypatch.setattr(pl, "pallas_call", pallas_call)
    monkeypatch.setattr(engine_mod, "ragged_grid_counts", counts)
    tr = SpanTracer(clock=VirtualClock()).enable()
    # programs of its own: ``pallas_call`` is patched in before the trace
    eng = _engine(model, jit_cache={}, tracer=tr, prefill_chunk=32,
                  prefix_block_size=8)
    eng.generate(_reqs())
    disp = [e["args"] for e in tr.events() if e["name"] == "dispatch"]
    assert len(disp) == len(asked) == eng.stats["unified_steps"] > 0
    # a program a packed size, traced by the first step that ran it, which
    # the engine counts before it calls: every layer call of one program was
    # built with one tiling, each size with its own
    marks = [m for *_, m in asked] + [len(built)]
    tiling = {}
    for i, (_, _, _, kw, _) in enumerate(asked):
        new = set(built[marks[i]:marks[i + 1]])
        if new:
            assert kw["packed_tokens"] not in tiling        # traced once
            tiling[kw["packed_tokens"]] = new
    assert set(tiling) == set(eng.step_rows) and len(tiling) == 2
    assert all(len(t) == 1 for t in tiling.values()), tiling
    assert all(pages > 1 for t in tiling.values() for _, pages in t)
    keys = ("grid_steps", "live_steps", "update_steps", "one_token_rows",
            "span_row_groups", "kv_tokens", "attn_pairs", "prefetched_pairs")
    for a, (qstart, qlen, kvlen, kw, _) in zip(disp, asked):
        assert a["packed_rows"] == kw["packed_tokens"]
        (block_q, pages), = tiling[a["packed_rows"]]
        kw = dict(kw, block_q=block_q, pages=pages)
        assert {k: a[k] for k in keys} \
            == _brute_force(qstart, qlen, kvlen, **kw)
    assert any(a["update_steps"] < a["live_steps"] for a in disp)


class TestGatewaySpansAndCounter:
    def test_loop_span_counter_and_no_rate_gauge(self, model):
        srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX,
                    prefill_chunk=32, trace=True)
        try:
            gw = srv.gateway
            assert gw.tracer.annotate is jax.profiler.TraceAnnotation
            for s in [gw.submit(r) for r in _reqs()]:
                s.result()
            # a stream's result arrives inside its last step: let the
            # driver finish that step and exit before anything is read
            assert gw.shutdown(drain=True, timeout=60)
            assert gw._loop_span is None
            evs = gw.tracer.events()
            loops = [e for e in evs if e["name"] == "loop"]
            steps = sorted((e for e in evs if e["name"] == "step"),
                           key=lambda e: e["ts"])
            assert loops and all(e["tid"] == TID_GATEWAY for e in loops)
            assert len(loops) == len(steps)
            # a loop span starts where a step ended, and ends before the
            # next step starts
            ends = [s["ts"] + s["dur"] for s in steps]
            for lp in loops:
                assert any(e <= lp["ts"] + 1e-3 for e in ends)
                later = [s["ts"] for s in steps if s["ts"] >= lp["ts"]]
                if later:
                    assert lp["ts"] + lp["dur"] <= min(later) + 1e-3
            text = gw.registry.render()
            fams = parse_prometheus(text)
            series = {labels: v for (_, labels), v in
                      fams["serving_step_tokens_total"]["samples"].items()}
            assert set(series) == {(("kind", "decode"),),
                                   (("kind", "prefill"),)}
            assert sum(series.values()) \
                == sum(s["args"]["tokens"] for s in steps)
            assert series[("kind", "prefill"),] == 80
            assert "serving_tokens_per_second" not in text
        finally:
            srv.shutdown(drain=False, timeout=30)


class TestDebugXplane:
    def test_capture_returns_the_directory_and_the_spans(self, model,
                                                         tmp_path):
        srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX)
        # The engine is kept stepping until the capture has returned. A
        # stream of a fixed length races the profiler: starting and
        # stopping a session takes seconds on a busy host, a 240-token
        # stream of this model about as long, and a capture armed after
        # the stream's end sees no step before its timeout.
        captured = threading.Event()

        def keep_stepping():
            while not captured.is_set():
                srv.gateway.submit(GenerationRequest(
                    prompt=[9, 10, 11, 12], max_new_tokens=16)).result()

        feeder = threading.Thread(target=keep_stepping, daemon=True)
        try:
            srv.gateway.submit(GenerationRequest(
                prompt=[1, 2, 3, 4], max_new_tokens=2)).result()
            feeder.start()
            # a profiler session someone else holds is a busy capture
            jax.profiler.start_trace(str(tmp_path))
            try:
                with pytest.raises(urllib.error.HTTPError) as busy:
                    urllib.request.urlopen(
                        srv.url + "/debug/xplane?steps=1", timeout=60)
                assert busy.value.code == 409
                assert srv.gateway._capture is None
            finally:
                jax.profiler.stop_trace()
            try:
                with urllib.request.urlopen(
                        srv.url + "/debug/xplane?steps=3&timeout_s=60",
                        timeout=120) as r:
                    doc = json.load(r)
            finally:
                captured.set()
            feeder.join(timeout=60)
            assert not feeder.is_alive()
            xdir = doc["otherData"]["xplane_dir"]
            assert glob.glob(os.path.join(xdir, "plugins", "profile", "*",
                                          "*.xplane.pb"))
            steps = [e for e in doc["traceEvents"] if e["name"] == "step"]
            assert len(steps) == 3
            assert srv.gateway.tracer.enabled is False
            from jax.profiler import ProfileData
            path = glob.glob(os.path.join(xdir, "plugins", "profile", "*",
                                          "*.xplane.pb"))[0]
            names = {ev.name for plane in ProfileData.from_file(path).planes
                     if plane.name == "/host:CPU"
                     for line in plane.lines for ev in line.events}
            assert {"step", "sweep", "plan", "launch", "dispatch", "call",
                    "device-wait", "host-accept", "retire", "loop"} <= names
            with pytest.raises(urllib.error.HTTPError) as bad:
                urllib.request.urlopen(srv.url + "/debug/xplane?steps=0")
            assert bad.value.code == 400
        finally:
            captured.set()
            srv.shutdown(drain=False, timeout=30)


# ------------------------------------------------ the kernel's work counter
def _live_pairs(qstart, qlen, kvlen, heads, block_q, block_size,
                table_entries, packed_tokens):
    """The kernel's own masks, token by token: ``{(query block, row): KV
    blocks}`` for every pair whose query block holds a token of the row's
    span, the blocks being those with a column some such token may see
    (``col <= pos`` and ``col < kvlen``). Returns the pairs and ``nq``."""
    bq = _query_block(block_q, heads, packed_tokens)
    nq = -(-(packed_tokens * heads) // bq)
    tpb = bq // heads
    pairs = {}
    for qi in range(nq):
        for r in range(len(qstart)):
            pos = [kvlen[r] - qlen[r] + (t - qstart[r])
                   for t in range(qstart[r], qstart[r] + qlen[r])
                   if qi * tpb <= t < (qi + 1) * tpb]
            if pos:
                pairs[qi, r] = sum(
                    any(ki * block_size <= min(p, kvlen[r] - 1) for p in pos)
                    for ki in range(table_entries))
    return pairs, nq


def _span_row_groups(walked, qstart, qlen, kvlen, pages, one_token, *,
                     kv_heads, heads, block_q, block_size, packed_tokens,
                     **_):
    """What the general walk's updates work on, plane row by plane row: for
    every pair that takes that walk, each row chunk of the block's planes
    (``_row_chunks``) counts its rows, in each of the ``kv_heads`` planes,
    once for every update whose first key
    a span token owning a row of the chunk may see; a plane that is one
    chunk counts in every update of the pair."""
    g = heads // kv_heads
    tpb = _query_block(block_q, heads, packed_tokens) // heads
    chunks = _row_chunks(_plane_rows(tpb * heads, heads, g, packed_tokens))
    total = 0
    for (qi, r), n in walked.items():
        if qlen[r] == 1 and one_token:
            continue
        for c0, rows in chunks:
            first = qi * tpb * g + c0
            pos = [kvlen[r] - qlen[r] + (j // g - qstart[r])
                   for j in range(first, first + rows)
                   if qstart[r] <= j // g < qstart[r] + qlen[r]]
            total += kv_heads * rows * sum(
                len(chunks) == 1 or any(u * pages * block_size <= p
                                        for p in pos)
                for u in range(-(-n // pages)))
    return total


def _brute_force(qstart, qlen, kvlen, pages=1, one_token=False,
                 kv_heads=None, **geometry):
    """The steps the kernel visits: its work list (one entry a query block
    or a row more than the pairs can ever be) plus every KV block its loops
    walk; only the latter compute, ``pages`` of them an online-softmax
    update (block by block: a new update starts at a pair's first block and
    after every ``pages``). A row takes the one-token walk where its span is
    one token and the kernel has that walk: ``one_token``, as the kernel's
    ``grid_params`` says. ``prefetched_pairs``: the list in its order (by
    query block, then row; a query block no span touches is one dead entry),
    a pair counted where it and the entry before it both walk something."""
    walked, nq = _live_pairs(qstart, qlen, kvlen, **geometry)
    entries = []
    for qi in range(nq):
        entries += [n for (b, _), n in sorted(walked.items())
                    if b == qi] or [0]
    live = sum(walked.values())
    updates = sum(ki % pages == 0 for n in walked.values()
                  for ki in range(n))
    pairs = sum(sum(kl - ql + i + 1 for i in range(ql))
                for ql, kl in zip(qlen, kvlen) if ql)
    return {"grid_steps": nq + len(qstart) + live,
            "live_steps": live, "update_steps": updates,
            "one_token_rows": sum(ql == 1 for ql in qlen)
            if one_token else 0,
            "span_row_groups": _span_row_groups(
                walked, qstart, qlen, kvlen, pages, one_token,
                kv_heads=kv_heads, **geometry) if kv_heads else 0,
            "kv_tokens": sum(kl for ql, kl in zip(qlen, kvlen) if ql),
            "attn_pairs": pairs,
            "prefetched_pairs": sum(a > 0 and b > 0 for a, b
                                    in zip(entries, entries[1:]))}


GRID_CASES = {
    "decode_only": ([0, 1, 2, 0], [1, 1, 1, 0], [17, 64, 65, 0]),
    "chunk_only": ([0, 0, 0, 0], [0, 40, 0, 0], [0, 104, 0, 0]),
    "mixed": ([0, 1, 2, 0], [1, 1, 37, 0], [200, 33, 37, 0]),
    # a dead row (qlen 0) whose stale qstart and kvlen point inside a
    # query block: it is on no work-list entry and walks nothing
    "dead_rows": ([0, 3, 9, 1], [1, 0, 0, 2], [9, 50, 0, 2]),
    "all_dead": ([0, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(GRID_CASES))
@pytest.mark.parametrize("heads,block_q", [(4, 16), (32, 256), (3, 8)])
def test_ragged_grid_counts_equals_enumeration(case, heads, block_q):
    qstart, qlen, kvlen = GRID_CASES[case]
    kw = dict(heads=heads, block_q=block_q, block_size=16,
              table_entries=8, packed_tokens=40,
              one_token=_one_token_walk(
                  heads, _query_block(block_q, heads, 40)))
    assert ragged_grid_counts(np.asarray(qstart), np.asarray(qlen),
                              np.asarray(kvlen), **kw) \
        == _brute_force(qstart, qlen, kvlen, **kw)


@pytest.mark.parametrize("case", sorted(GRID_CASES))
@pytest.mark.parametrize("heads,block_q,pages", [
    (4, 16, 3), (32, 256, 2), (16, 64, 8), (32, 32, 5)])
def test_ragged_grid_counts_updates_and_one_token_rows(case, heads, block_q,
                                                       pages):
    """The two numbers PR 32 added: an update a group of ``pages`` blocks
    (one block an update at ``pages=1``, so ``update_steps == live_steps``
    there), and the rows on the one-token walk (none where ``heads`` is not
    whole tiles, 4, or is the whole query block, 32 of 32); the older keys
    do not depend on ``pages``."""
    qstart, qlen, kvlen = GRID_CASES[case]
    kw = dict(heads=heads, block_q=block_q, block_size=16,
              table_entries=8, packed_tokens=40,
              one_token=_one_token_walk(
                  heads, _query_block(block_q, heads, 40)))
    got = ragged_grid_counts(qstart, qlen, kvlen, pages=pages, **kw)
    assert got == _brute_force(qstart, qlen, kvlen, pages=pages, **kw)
    one = ragged_grid_counts(qstart, qlen, kvlen, **kw)
    assert one["update_steps"] == one["live_steps"]
    assert {k: v for k, v in got.items() if k != "update_steps"} \
        == {k: v for k, v in one.items() if k != "update_steps"}


@pytest.mark.parametrize("case", sorted(GRID_CASES))
@pytest.mark.parametrize("heads,kv_heads,block_q,pages", [
    (4, 4, 16, 3), (32, 8, 256, 2), (16, 1, 64, 8), (20, 1, 32 * 20, 2),
    (20, 10, 24 * 20, 1), (32, 2, 40 * 32, 3)])
def test_ragged_grid_counts_span_row_groups(case, heads, kv_heads, block_q,
                                            pages):
    """The number PR 53 added: the plane rows the general walk's updates
    work on, by the kernel's own predicates (a plane in row chunks where it
    is taller than 512 rows: 20 / 1 and 32 / 2 here, whose chunks with no
    row of the span or no key under their last row's diagonal count
    nothing), and 0 without ``kv_heads``; the older keys do not depend on
    it."""
    qstart, qlen, kvlen = GRID_CASES[case]
    g = heads // kv_heads
    tiling = grid_params("float32", 16, kv_heads * 16, 8, heads, 40, block_q,
                         pages, head_dim=16)
    kw = dict(heads=heads, block_size=16, table_entries=8, packed_tokens=40,
              **tiling)
    got = ragged_grid_counts(qstart, qlen, kvlen, kv_heads=kv_heads, **kw)
    assert got == _brute_force(qstart, qlen, kvlen, kv_heads=kv_heads, **kw)
    assert (len(_row_chunks(_plane_rows(tiling["block_q"], heads, g, 40)))
            > 1) == ((heads, kv_heads) in [(20, 1), (32, 2)])
    spans = [ql for ql in qlen if ql > 1 or (ql and not tiling["one_token"])]
    assert (got["span_row_groups"] > 0) == bool(spans)
    without = ragged_grid_counts(qstart, qlen, kvlen, **kw)
    assert without == dict(got, span_row_groups=0)


# the serving cells' own geometry (benchmark/configs: 32 heads a chip, pool
# blocks of 32, 8 slots x 128 table entries, 8 + 512 packed tokens) and a
# step of each cell's kind, with the most grid steps a call may take
CELL_STEPS = {
    "chat_six_decode_rows": (
        [0, 1, 2, 3, 4, 5, 0, 0], [1, 1, 1, 1, 1, 1, 0, 0],
        [200, 311, 427, 512, 640, 768, 0, 0], 2_000),
    "batch_chunk_and_decode_row": (
        [1, 0, 0, 0, 0, 0, 0, 0], [484, 1, 0, 0, 0, 0, 0, 0],
        [1508, 2307, 0, 0, 0, 0, 0, 0], 10_000),
}


@pytest.mark.parametrize("step", sorted(CELL_STEPS))
def test_ragged_grid_counts_at_the_cells_geometry(step):
    qstart, qlen, kvlen, most = CELL_STEPS[step]
    # Mistral's 32 / 8 / 128: 8 pages an update in query blocks of 128 tokens
    # (512 rows of each KV head's plane); OLMoE's 16 / 16 / 128 takes 4 pages
    # and 256 tokens, Olmo-Hybrid's 30 / 30 / 128 4 pages (a lane tile of
    # keys, the least) and 128 tokens; a one-byte pool counts as float32
    pages, block_q = pages_per_update("bfloat16", 32, 8 * 128, 128), \
        query_block_rows(32, 128)
    assert (pages, block_q) == (8, 128 * 32)
    # the call's tiling is those two, fitted to the heads and the table,
    # and whether a decode row has a row tile of its own (at 3 heads a KV
    # head one of 48 rows, 16 tokens in 3 row tiles, since PR 51)
    tiling = grid_params("bfloat16", 32, 8 * 128, 128, 32, 520, head_dim=128)
    assert tiling == dict(block_q=128 * 32, pages=8, one_token=True)
    assert grid_params("bfloat16", 32, 8 * 128, 128, 24, 520, pages=999,
                       head_dim=128) \
        == dict(block_q=160 * 24, pages=128, one_token=True)
    assert grid_params("bfloat16", 32, 8 * 128, 128, 32, 8, head_dim=128) \
        == dict(block_q=8 * 32, pages=8, one_token=True)
    assert (pages_per_update("bfloat16", 32, 16 * 128, 64),
            query_block_rows(16, 128)) == (4, 256 * 16)
    assert (pages_per_update("bfloat16", 32, 30 * 128, 72),
            query_block_rows(30, 128)) == (4, 128 * 30)
    # Phi-4-mini-flash's 40 / 10 over pairs of 128 (and as 20 / 10 / 128),
    # 6 pages: the four geometries above are what they were before PR 51
    assert (pages_per_update("bfloat16", 32, 10 * 128, 256),
            query_block_rows(40, 128),
            query_block_rows(20, 128)) == (6, 96 * 40, 192 * 20)
    # the two whose block the accumulator alone sizes since PR 51 (a plane
    # cut to 512 rows held 32 and 16 tokens): Nemotron-3-Nano's 32 / 2 / 128
    # and Jamba2-3B's 20 / 1 / 128, whose decode rows have a tile of 80 rows
    assert (pages_per_update("bfloat16", 32, 2 * 128, 192),
            query_block_rows(32, 128)) == (8, 128 * 32)
    assert (pages_per_update("bfloat16", 32, 128, 1024),
            query_block_rows(20, 128)) == (8, 192 * 20)
    assert pages_per_update("int8", 32, 8 * 128, 128) == 4
    assert pages_per_update("float32", 16, 128, 5) == 5
    kw = dict(heads=32, block_size=32, table_entries=128, packed_tokens=520)
    got = ragged_grid_counts(qstart, qlen, kvlen, **kw, **tiling)
    assert got == _brute_force(qstart, qlen, kvlen, **kw, **tiling)
    assert 5 + 8 < got["grid_steps"] < most
    assert got["live_steps"] == got["grid_steps"] - (5 + 8)
    assert got["live_steps"] / 8 <= got["update_steps"] \
        < got["live_steps"] / 4
    assert got["one_token_rows"] == sum(n == 1 for n in qlen)


CELL_TILINGS = {
    # cell's model: (pool row, table entries, heads, packed tokens), tiling
    "mistral": ((8 * 128, 128, 32, 520),
                dict(block_q=128 * 32, pages=8, one_token=True)),
    "olmoe": ((16 * 128, 64, 16, 536),
              dict(block_q=256 * 16, pages=4, one_token=True)),
    "olmo_hybrid": ((30 * 128, 72, 30, 544),
                    dict(block_q=128 * 30, pages=4, one_token=True)),
    "phi4_flash": ((10 * 128, 256, 40, 560),
                   dict(block_q=96 * 40, pages=6, one_token=True)),
    "phi4_flash_as_pairs": ((10 * 128, 256, 20, 560),
                            dict(block_q=192 * 20, pages=6, one_token=True)),
    "nemotron3_nano": ((2 * 128, 192, 32, 544),
                       dict(block_q=128 * 32, pages=8, one_token=True)),
    "jamba2": ((128, 1024, 20, 528),
               dict(block_q=192 * 20, pages=8, one_token=True)),
    "jamba2_decode_only": ((128, 1024, 20, 16),
                           dict(block_q=16 * 20, pages=8, one_token=True)),
}


@pytest.mark.parametrize("cell", sorted(CELL_TILINGS))
def test_grid_params_at_every_dense_cells_geometry(cell):
    """The call's tiling at each dense serving cell's geometry (bfloat16
    pools in blocks of 32, heads of 128): the first five are what they were
    before PR 51 sized the query block by its accumulator alone; Nemotron's
    block held 32 tokens and Jamba's 16, with no tile for a decode row."""
    (kd, entries, heads, packed), tiling = CELL_TILINGS[cell]
    assert grid_params("bfloat16", 32, kd, entries, heads, packed,
                       head_dim=128) == tiling


def test_ragged_grid_counts_at_jambas_geometry():
    """A step of ``serve-jamba2-longdoc-prefill``: 8 decode rows 16k into
    their documents and a 512-token chunk 8k into its own, 20 heads on one
    KV head, tables of 1,024 blocks of 32. The chunk meets 3 query blocks of
    192 tokens, each walking the prefix to its own diagonal (33 + 34 + 34
    updates of 256 keys), and every decode row its own 65 on its own row
    tile; at 16 tokens a block (a plane cut to 512 rows, before PR 51) the
    chunk met 33 blocks and no row had a tile."""
    qstart = list(range(8)) + [8] + [0] * 7
    qlen = [1] * 8 + [512] + [0] * 7
    kvlen = [16_384 + 1] * 8 + [8_192 + 512] + [0] * 7
    tiling = grid_params("bfloat16", 32, 128, 1024, 20, 528, head_dim=128)
    kw = dict(heads=20, block_size=32, table_entries=1024, packed_tokens=528)
    kw["kv_heads"] = 1
    got = ragged_grid_counts(qstart, qlen, kvlen, **kw, **tiling)
    assert got == _brute_force(qstart, qlen, kvlen, **kw, **tiling)
    assert got["one_token_rows"] == 8
    assert got["update_steps"] == 8 * 65 + 33 + 34 + 34
    # the chunk's 10,240 plane rows in 480-row chunks of its three blocks,
    # each chunk in the updates up to its own last row's diagonal, 33 to 35
    # of them (101 updates of whole 3,840-row blocks would be 387,840); the
    # decode rows walk on their own tiles
    assert got["span_row_groups"] == 353_760
    old = dict(block_q=16 * 20, pages=8, one_token=False)
    before = ragged_grid_counts(qstart, qlen, kvlen, **kw, **old)
    assert before == _brute_force(qstart, qlen, kvlen, **kw, **old)
    assert before["one_token_rows"] == 0
    assert before["update_steps"] == 8 * 65 + 1_106
    assert before["attn_pairs"] == got["attn_pairs"]
    assert before["kv_tokens"] == got["kv_tokens"]


# ---------------------------------------------- names on the device's work
class TestNamesInTheProgram:
    def test_ragged_step_names_its_kernel_and_blocks(self, model):
        eng = _engine(model)
        assert model.config.decode_attention == "pallas"
        R, T = NUM_SLOTS, eng._token_budget
        z = lambda n, dt=np.int32: np.zeros(n, dt)      # noqa: E731
        text = eng._ragged_fn(1, T).lower(
            eng._params, *eng.cache.kv_args(), eng.cache.tables, z(T),
            np.full(T, R, np.int32), z(T), z(R), z(R), z(R), z(R),
            np.asarray(eng._keys, np.uint32), z(R, np.float32),
            z(R), z(R), z(R), z((R, 2), np.uint32),
            z(R)).as_text(debug_info=True)
        for scope in ("ragged_step", "attn", "mlp", "lm_head", "sample",
                      "ragged_paged_attention"):
            # a scan body's ops start their name at the body's own scope
            assert f"/{scope}/" in text or f'"{scope}/' in text, scope

    def test_train_step_names_its_phases_and_kernels(self, monkeypatch):
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.kernels import flash_attention
        from paddle_tpu.optimizer import AdamW
        monkeypatch.setattr(flash_attention, "_use_pallas", lambda s: True)
        m = serving_support.fresh_model("llama", seed=0,     # it is trained
                                        attention_layout="bhsd")
        step = TrainStep(m, lambda loss, _lab: loss,
                         AdamW(parameters=m.parameters(),
                               learning_rate=1e-3))
        ids = jnp.zeros((2, 128), jnp.int32)
        text = step._compiled.lower(
            step._params, step._buffers, step._opt_state, (ids, ids),
            (ids,), np.float32(1e-3),
            jax.random.PRNGKey(0)).as_text(debug_info=True)
        for name in ("/optimizer/", "jvp(loss)", "transpose(jvp(loss))",
                     "rematted_computation", "/mlp/", "flash_fwd",
                     "flash_bwd_dkv", "flash_bwd_dq"):
            assert name in text, name
        # and the step itself is one named annotation a step
        seen = []
        monkeypatch.setattr(
            jax.profiler, "StepTraceAnnotation",
            lambda name, **kw: seen.append((name, kw)) or NULL_SPAN)
        step.step((ids, ids), (ids,))
        step.step((ids, ids), (ids,))
        assert seen == [("train_step", {"step_num": 0}),
                        ("train_step", {"step_num": 1})]
