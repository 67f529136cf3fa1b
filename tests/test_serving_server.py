"""Async serving gateway (serving/server/): localhost integration tests.

The properties under test, per the serving contract:

- HTTP output is the ENGINE's output: blocking and SSE completions
  reproduce ``engine.generate()`` token-for-token for the same seeded
  request (the gateway adds no device work and no nondeterminism);
- cancellation (client disconnect or handle.cancel()) frees the KV slot
  mid-decode (``num_free`` recovers) and never perturbs other streams;
- deadlines expire queued AND running requests with
  ``finish_reason="timeout"``;
- admission control sheds load at the waiting-room bound (429);
- ``GET /metrics`` renders valid Prometheus text (validated by the
  strict parser from test_metrics_prom) with the serving series;
- graceful drain finishes in-flight work and 503s new work;
- the compile-once contract survives mixed HTTP traffic: varied
  sampling knobs, prompt lengths, a cancellation and a timeout leave
  ``decode_compilations() == 1``.
"""
import http.client
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.profiler.metrics import MetricsRegistry
from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.server import (QueueFullError, ServingGateway,
                                       ServingHTTPServer, TokenStream, serve)
from paddle_tpu.serving.server.sse import StreamWriter

import serving_support
from serving_support import wait_until
from test_metrics_prom import parse_prometheus

NUM_SLOTS, S_MAX, MAX_QUEUE = 2, 128, 4


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=21)  # GQA, pallas decode


def _engine(model):
    """The shared helper at what ``serve()`` is given below: 128 positions
    and the ENGINE's own chunk and block, one step a call."""
    return serving_support.engine_as_given(
        model, num_slots=NUM_SLOTS, max_seq_len=S_MAX, decode_chunk=1)


@pytest.fixture(scope="module")
def server(model):
    srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX,
                max_queue=MAX_QUEUE, model_name="llama-tiny-test")
    # warm every program shape the tests hit (decode, prefill groups of
    # 1 and 2) so latency-sensitive cases measure steps, not compiles
    a = srv.gateway.submit(GenerationRequest(prompt=_prompt(0),
                                             max_new_tokens=2))
    b = srv.gateway.submit(GenerationRequest(prompt=_prompt(1),
                                             max_new_tokens=2))
    a.result(), b.result()
    yield srv
    srv.shutdown(drain=False, timeout=30)


def _prompt(seed, n=8):
    return serving_support.prompt(seed, n).tolist()


def _direct(model, req):
    """The oracle: the same request straight through the engine."""
    out = _engine(model).generate([req])[0]
    return out.tolist(), out.finish_reason


def _post(server, payload, timeout=120):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        server.url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e), dict(e.headers)


def _sse(server, payload, timeout=120):
    """POST with stream=true; return (tokens, finish_reason, usage)."""
    body = json.dumps(dict(payload, stream=True)).encode()
    req = urllib.request.Request(server.url + "/v1/completions", data=body)
    toks, reason, usage = [], None, None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        for line in r:
            line = line.decode().strip()
            if not line.startswith("data: "):
                continue
            data = line[len("data: "):]
            if data == "[DONE]":
                break
            ev = json.loads(data)
            ch = ev["choices"][0]
            if ch["finish_reason"] is not None:
                reason, usage = ch["finish_reason"], ev.get("usage")
            elif ch["token_id"] is not None:
                toks.append(ch["token_id"])
    return toks, reason, usage


class TestCompletions:
    def test_blocking_matches_direct_engine(self, model, server):
        req = GenerationRequest(prompt=_prompt(2), max_new_tokens=6)
        want, want_reason = _direct(model, req)
        status, doc, _ = _post(server, {"prompt": _prompt(2),
                                        "max_tokens": 6})
        assert status == 200 and doc["object"] == "text_completion"
        choice = doc["choices"][0]
        assert choice["token_ids"] == want
        assert choice["finish_reason"] == want_reason == "length"
        assert doc["usage"] == {"prompt_tokens": 8, "completion_tokens": 6,
                                "total_tokens": 14}

    def test_sse_stream_matches_direct_engine_sampled(self, model, server):
        """Seeded sampled request: the SSE token-by-token stream equals
        the offline engine run exactly — per-request key chains make
        tokens independent of serving-side batching."""
        knobs = dict(max_new_tokens=7, temperature=0.9, top_k=5, seed=123)
        want, _ = _direct(model, GenerationRequest(prompt=_prompt(3),
                                                   **knobs))
        toks, reason, usage = _sse(server, {
            "prompt": _prompt(3), "max_tokens": 7, "temperature": 0.9,
            "top_k": 5, "seed": 123})
        assert toks == want
        assert reason == "length"
        assert usage["completion_tokens"] == 7

    def test_eos_maps_to_stop(self, model, server):
        free = _direct(model, GenerationRequest(prompt=_prompt(4),
                                                max_new_tokens=12))[0]
        eos = free[2]
        status, doc, _ = _post(server, {
            "prompt": _prompt(4), "max_tokens": 12, "eos_token_id": eos})
        assert status == 200
        choice = doc["choices"][0]
        assert choice["finish_reason"] == "stop"
        assert choice["token_ids"] == free[:free.index(eos) + 1]

    def test_validation_400(self, server):
        for bad in ({"max_tokens": 4},                       # no prompt
                    {"prompt": "text"},                      # not ids
                    {"prompt": [1, 2], "max_tokens": 0},
                    {"prompt": [1] * 200, "max_tokens": 8}):  # > cache
            status, doc, _ = _post(server, bad)
            assert status == 400, bad
            assert doc["error"]["type"] == "invalid_request"

    def test_unknown_routes_404(self, server):
        status, doc, _ = _post(server, {})
        assert status in (400, 404)
        try:
            urllib.request.urlopen(server.url + "/nope", timeout=10)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404

    def test_healthz(self, server):
        with urllib.request.urlopen(server.url + "/healthz",
                                    timeout=10) as r:
            doc = json.load(r)
        assert doc["status"] == "ok"
        assert doc["num_slots"] == NUM_SLOTS


class TestCancellation:
    def test_cancel_mid_stream_frees_slot(self, model, server):
        """Iterate a few tokens, cancel, and the slot returns to the
        free list while a concurrent stream finishes byte-identical to
        its solo run."""
        gw = server.gateway
        eng = gw.engine
        free0 = eng.cache.num_free
        bystander_req = GenerationRequest(prompt=_prompt(5),
                                          max_new_tokens=40)
        want, _ = _direct(model, bystander_req)
        bystander = gw.submit(GenerationRequest(prompt=_prompt(5),
                                                max_new_tokens=40))
        victim = gw.submit(GenerationRequest(prompt=_prompt(6),
                                             max_new_tokens=100))
        it = iter(victim)
        got = [next(it) for _ in range(3)]
        victim.cancel()
        # cancellation lands at the next step boundary: tokens already
        # decoded before it applies still stream out, then it stops
        tail = list(it)
        assert victim.finish_reason == "cancelled"
        assert len(got) == 3 and len(got) + len(tail) < 100
        ids, reason = bystander.result()
        assert ids.tolist() == want and reason == "length"
        wait_until(lambda: eng.cache.num_free == free0, "the slots back")

    def test_http_client_disconnect_cancels(self, server):
        """Dropping the SSE connection mid-stream cancels the request:
        the engine's cancelled counter ticks and the slot frees."""
        gw = server.gateway
        eng = gw.engine
        free0 = eng.cache.num_free
        cancelled0 = eng.stats["cancelled"]
        conn = http.client.HTTPConnection(server.host, server.port,
                                          timeout=30)
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt": _prompt(7), "max_tokens": 110, "stream": True}))
        resp = conn.getresponse()
        assert resp.status == 200
        # read a couple of SSE events, then vanish (closing with unread
        # data in the recv buffer RSTs the server's next write)
        resp.fp.readline(), resp.fp.readline()
        resp.close()
        conn.close()
        wait_until(lambda: eng.stats["cancelled"] != cancelled0,
                   "the cancellation")
        assert eng.stats["cancelled"] == cancelled0 + 1
        wait_until(lambda: eng.cache.num_free == free0, "the slots back")


class TestDeadlines:
    def test_running_timeout_over_http(self, server):
        eng = server.gateway.engine
        free0 = eng.cache.num_free
        status, doc, _ = _post(server, {
            "prompt": _prompt(8), "max_tokens": 119, "timeout_s": 0.05})
        assert status == 200
        choice = doc["choices"][0]
        assert choice["finish_reason"] == "timeout"
        assert 0 < len(choice["token_ids"]) < 119  # partial output kept
        wait_until(lambda: eng.cache.num_free == free0, "the slots back")

    def test_queued_timeout_never_claims_slot(self, server):
        """A request whose deadline expires while still queued times out
        without a prefill (the slot goes to live work instead)."""
        gw = server.gateway
        eng = gw.engine
        hogs = [gw.submit(GenerationRequest(prompt=_prompt(9 + i),
                                            max_new_tokens=60))
                for i in range(NUM_SLOTS)]
        while gw.queue_depth:          # hogs admitted to slots
            time.sleep(0.005)
        prefills0 = eng.stats["prefills"]
        doomed = gw.submit(GenerationRequest(
            prompt=_prompt(11), max_new_tokens=50, timeout_s=0.01))
        ids, reason = doomed.result()
        assert reason == "timeout" and len(ids) == 0
        for h in hogs:
            assert h.result()[1] == "length"  # bystanders unaffected
        assert eng.stats["prefills"] == prefills0 + 0  # doomed never prefilled


class TestAdmissionControl:
    def test_429_when_waiting_room_full(self, server):
        gw = server.gateway
        hogs = [gw.submit(GenerationRequest(prompt=_prompt(20 + i),
                                            max_new_tokens=100))
                for i in range(NUM_SLOTS)]
        while gw.queue_depth:
            time.sleep(0.005)
        queued = [gw.submit(GenerationRequest(prompt=_prompt(30 + i),
                                              max_new_tokens=4))
                  for i in range(MAX_QUEUE)]
        with pytest.raises(QueueFullError):
            gw.submit(GenerationRequest(prompt=_prompt(40),
                                        max_new_tokens=4))
        status, doc, headers = _post(server, {"prompt": _prompt(41),
                                              "max_tokens": 4})
        assert status == 429
        assert doc["error"]["type"] == "rate_limit"
        assert headers.get("Retry-After") == "1"
        for s in hogs + queued:        # drain so later tests start clean
            s.result()


class TestMetricsEndpoint:
    def test_scrape_parses_with_required_series(self, server):
        _post(server, {"prompt": _prompt(50), "max_tokens": 3})
        with urllib.request.urlopen(server.url + "/metrics",
                                    timeout=10) as r:
            assert r.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            text = r.read().decode()
        fams = parse_prometheus(text)  # strict: raises on format errors
        assert fams["serving_queue_depth"]["type"] == "gauge"
        assert fams["serving_active_slots"]["type"] == "gauge"
        assert fams["serving_num_slots"]["samples"][
            ("serving_num_slots", ())] == NUM_SLOTS
        assert fams["serving_generated_tokens_total"]["type"] == "counter"
        assert fams["serving_generated_tokens_total"]["samples"][
            ("serving_generated_tokens_total", ())] > 0
        lat = fams["serving_request_latency_seconds"]
        assert lat["type"] == "histogram"
        assert lat["samples"][
            ("serving_request_latency_seconds_count", ())] > 0
        ttft = fams["serving_ttft_seconds"]["samples"]
        assert ttft[("serving_ttft_seconds_count", ())] > 0
        # finish reasons accumulated under labels
        fin = fams["serving_finished_total"]["samples"]
        assert any(lab == (("reason", "length"),) for (_, lab) in fin)

    def test_scrape_counts_the_pipeline(self, server):
        """ISSUE 30: the step program goes to the chip one step ahead.
        /metrics says how often (``serving_steps_dispatched_ahead_total``
        over ``serving_step_duration_seconds_count``) and why the pipeline
        was emptied (``serving_pipeline_drains_total{reason}``)."""
        _post(server, {"prompt": _prompt(51), "max_tokens": 12})
        with urllib.request.urlopen(server.url + "/metrics",
                                    timeout=10) as r:
            fams = parse_prometheus(r.read().decode())
        ahead = fams["serving_steps_dispatched_ahead_total"]
        assert ahead["type"] == "counter"
        n_ahead = ahead["samples"][
            ("serving_steps_dispatched_ahead_total", ())]
        steps = fams["serving_step_duration_seconds"]["samples"][
            ("serving_step_duration_seconds_count", ())]
        # eleven decode programs, all but the first behind another one
        assert 10 <= n_ahead < steps
        drains = fams["serving_pipeline_drains_total"]
        assert drains["type"] == "counter"
        by_reason = {dict(lab)["reason"]: v
                     for (_, lab), v in drains["samples"].items()}
        assert set(by_reason) == {"idle", "cancel", "evict", "preempt",
                                  "pool", "deadline", "snapshot", "fault"}
        assert by_reason["idle"] >= 1 and by_reason["fault"] == 0
        # the histogram counts step programs fenced, each once, wherever
        # it was fenced: behind the next dispatch, or at a drain
        assert steps == n_ahead + sum(by_reason.values())


class TestGracefulDrain:
    def test_drain_finishes_inflight_then_503(self, model):
        """Own server: shutdown(drain=True) lets queued + running work
        finish (finish_reason intact, tokens consumable afterwards),
        then the front door 503s."""
        srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX,
                    max_queue=8, model_name="drain-test")
        gw = srv.gateway
        streams = [gw.submit(GenerationRequest(prompt=_prompt(60 + i),
                                               max_new_tokens=10 + i))
                   for i in range(4)]
        url = srv.url
        srv.shutdown(drain=True, timeout=60)
        assert [s.finish_reason for s in streams] == ["length"] * 4
        ids, _ = streams[2].result()   # events survive the drain
        assert len(ids) == 12
        with pytest.raises(Exception):
            gw.submit(GenerationRequest(prompt=_prompt(70),
                                        max_new_tokens=2))

    def test_shutdown_without_drain_cancels(self, model):
        srv = serve(model, port=0, num_slots=1, max_seq_len=S_MAX,
                    max_queue=8, model_name="cancel-test")
        gw = srv.gateway
        streams = [gw.submit(GenerationRequest(prompt=_prompt(80 + i),
                                               max_new_tokens=110))
                   for i in range(3)]
        srv.shutdown(drain=False, timeout=30)
        # everything not already finished was cancelled; nothing hangs
        assert all(s.finish_reason in ("cancelled", "length")
                   for s in streams)
        assert any(s.finish_reason == "cancelled" for s in streams)


class TestCompileOnce:
    def test_mixed_http_traffic_keeps_one_decode_trace(self, model):
        """The acceptance pin: varied sampling knobs, varied prompt
        lengths, a cancellation, and a timeout over HTTP leave
        ``decode_compilations() == 1`` — serving adds zero retraces."""
        from paddle_tpu.serving.server.gateway import ServingGateway
        eng = _engine(model)
        gw = ServingGateway(eng, max_queue=8)
        srv = ServingHTTPServer(gw, port=0).start()
        try:
            _post(srv, {"prompt": _prompt(90), "max_tokens": 5})
            assert eng.decode_compilations() == 1
            _post(srv, {"prompt": _prompt(91), "max_tokens": 9,
                        "temperature": 1.1, "top_k": 7, "seed": 4})
            _post(srv, {"prompt": _prompt(92, n=13), "max_tokens": 3,
                        "temperature": 0.4, "seed": 9})
            toks, reason, _ = _sse(srv, {"prompt": _prompt(93, n=5),
                                         "max_tokens": 6, "seed": 1,
                                         "temperature": 0.7, "top_k": 3})
            assert len(toks) == 6 and reason == "length"
            # cancellation leg
            victim = gw.submit(GenerationRequest(prompt=_prompt(94),
                                                 max_new_tokens=100))
            next(iter(victim))
            victim.cancel()
            # timeout leg
            _, t_reason = gw.submit(GenerationRequest(
                prompt=_prompt(95), max_new_tokens=119,
                timeout_s=0.05)).result()
            assert t_reason == "timeout"
            assert eng.decode_compilations() == 1  # the whole point
        finally:
            srv.shutdown(drain=False, timeout=30)


# ---------------------------------------------------------------------------
# One writer for every SSE stream (serving/server/sse.py): a streaming
# response's handler registers its stream and socket and parks; the driver
# hands a step's events over in one batch and writes them.


def _parent_event(obj):
    """One SSE event as the handler thread wrote it before the writer."""
    data = obj if isinstance(obj, str) else json.dumps(obj)
    return f"data: {data}\n\n".encode()


class _NoGateway:
    """What a bare ``TokenStream`` asks of its gateway: ``cancel()`` wakes
    the driver."""

    def __init__(self):
        self._wake = threading.Event()


class _Wire:
    """A ``TokenStream`` driven by hand, its response's socket pair and the
    handler thread parked in ``StreamWriter.serve``."""

    def __init__(self, writer, stream_id="cmpl-7", model_name="m",
                 prompt_tokens=3):
        self.writer = writer
        self.stream = TokenStream(_NoGateway(), None, stream_id)
        self.server_side, self.client = socket.socketpair()
        self.client.settimeout(30)
        self.handler = threading.Thread(
            target=writer.serve, daemon=True,
            args=(self.stream, self.server_side, model_name, prompt_tokens))

    def push(self, *events):
        """The driver's side: push, then hand over in one batch."""
        batch = []
        for kind, payload in events:
            getattr(self.stream, "_push_" + kind)(payload, batch)
        if batch:
            self.writer.write(batch)

    def read_to_done(self):
        data = b""
        while not data.endswith(b"data: [DONE]\n\n"):
            got = self.client.recv(65536)
            assert got, f"closed before [DONE]: {data!r}"
            data += got
        return data

    def close(self):
        self.client.close()
        self.handler.join(30)       # gone with its client, at the latest
        self.server_side.close()


def _token_ids(body):
    """The token ids of a whole streamed body's token events: all but the
    finish event, [DONE] and the empty tail."""
    return [json.loads(f[len(b"data: "):])["choices"][0]["token_id"]
            for f in body.split(b"\n\n")[:-3]]


@pytest.fixture()
def writer():
    w = StreamWriter(MetricsRegistry())
    yield w
    w.close(timeout=5)


class TestStreamFrames:
    """The bytes of a streamed response are what the handler thread wrote
    for the same tokens: one event a token, today's keys in today's order."""

    TOKEN = (b'data: {"id": "cmpl-7", "object": "text_completion.chunk", '
             b'"model": "m", "choices": [{"index": 0, "token_id": %d, '
             b'"finish_reason": null}]}\n\n')
    FINISH = (b'data: {"id": "cmpl-7", "object": "text_completion.chunk", '
              b'"model": "m", "choices": [{"index": 0, "token_id": null, '
              b'"finish_reason": "length"}], "usage": {"prompt_tokens": 3, '
              b'"completion_tokens": 3, "total_tokens": 6}}\n\n')
    ERROR = (b'data: {"id": "cmpl-7", "object": "text_completion.chunk", '
             b'"model": "m", "choices": [{"index": 0, "token_id": null, '
             b'"finish_reason": "error"}], "error": {"message": '
             b'"engine driver died: boom", "type": "server_error"}}\n\n')
    DONE = b"data: [DONE]\n\n"

    @pytest.mark.parametrize("last, want", [
        (("finish", "length"), FINISH), (("error", "engine driver died: boom"),
                                         ERROR)], ids=["finish", "error"])
    def test_bytes_equal_the_parents(self, writer, last, want):
        wire = _Wire(writer)
        wire.handler.start()
        wait_until(lambda: wire.stream._sink is not None, "the registration")
        tokens = (5, 0, 255999)
        for t in tokens:        # a step each: one event a hand-over
            wire.push(("token", t))
        wire.push(last)
        got = wire.read_to_done()
        assert got == b"".join(self.TOKEN % t for t in tokens) + want \
            + self.DONE
        # and the literal is what json.dumps of the parent's dicts gave
        chunk = {"id": "cmpl-7", "object": "text_completion.chunk",
                 "model": "m",
                 "choices": [{"index": 0, "token_id": 5,
                              "finish_reason": None}]}
        assert _parent_event(chunk) == self.TOKEN % 5
        assert _parent_event("[DONE]") == self.DONE
        wire.handler.join(30)
        assert not wire.handler.is_alive()      # parked until the last frame
        wire.close()

    @pytest.mark.parametrize("model_name", [
        'quo"te', "uniçode", 'has "token_id": 0 inside'])
    def test_a_token_frame_is_json_dumps_of_its_chunk(self, writer,
                                                      model_name):
        """The frame is built from a prefix and a suffix around the
        integer: whatever the model's name holds, it is the dump."""
        wire = _Wire(writer, model_name=model_name)
        wire.handler.start()
        wait_until(lambda: wire.stream._sink is not None, "the registration")
        wire.push(("token", 41), ("finish", "stop"))
        got = wire.read_to_done()
        chunk = {"id": "cmpl-7", "object": "text_completion.chunk",
                 "model": model_name,
                 "choices": [{"index": 0, "token_id": 41,
                              "finish_reason": None}]}
        assert got.startswith(_parent_event(chunk))
        assert json.loads(got.split(b"\n\n")[1][len(b"data: "):])[
            "usage"]["completion_tokens"] == 1
        wire.close()

    @pytest.mark.parametrize("queued", [1, 3])
    def test_tokens_pushed_before_registration_come_first_and_once(
            self, writer, queued):
        """The driver produced tokens before the handler registered: the
        registration drains the stream's queue ahead of every later
        batch."""
        wire = _Wire(writer)
        wire.push(*[("token", 100 + i) for i in range(queued)])
        assert wire.stream._sink is None        # queued, nothing written
        wire.handler.start()
        wait_until(lambda: wire.stream._sink is not None, "the registration")
        wire.push(("token", 7), ("finish", "length"))
        got = wire.read_to_done()
        assert _token_ids(got) == [100 + i for i in range(queued)] + [7]
        wire.close()

    def test_a_finished_stream_registers_and_ends(self, writer):
        """Everything was queued before the handler came: the registration
        alone writes the whole response and the handler never parks."""
        wire = _Wire(writer)
        wire.push(("token", 9), ("finish", "stop"))
        wire.handler.start()
        got = wire.read_to_done()
        assert got.count(b"data: ") == 3
        wire.handler.join(30)
        assert not wire.handler.is_alive()
        assert writer._m_batches.value() == 1
        assert writer._m_events.value() == 2
        assert writer._thread is None       # nobody fell behind
        wire.close()

    def test_the_library_handle_keeps_its_iterator(self):
        """No sink attached: events reach the queue and ``for token in
        stream`` / ``result()`` as before, and nothing enters a batch."""
        stream = TokenStream(_NoGateway(), None, "cmpl-1")
        batch = []
        stream._push_token(4, batch)
        stream._push_token(6, batch)
        stream._push_finish("length", batch)
        assert batch == []
        ids, reason = stream.result()
        assert ids.tolist() == [4, 6] and reason == "length"

    def test_a_reader_that_is_behind_keeps_its_own_buffer(self, writer):
        """A send that would block leaves the bytes in the stream's buffer,
        counted; the selector thread drains it once the reader reads, and
        the other stream of the batch never waits."""
        slow, fast = _Wire(writer, "cmpl-8"), _Wire(writer, "cmpl-9")
        for w in (slow, fast):
            w.server_side.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                     4096)
            w.handler.start()
            wait_until(lambda: w.stream._sink is not None, "registration")
        n = 0
        while writer._m_backlogged.value() == 0:
            batch = []
            slow.stream._push_token(n, batch)
            fast.stream._push_token(n, batch)
            writer.write(batch)
            assert fast.client.recv(65536)      # fast reads as it goes
            n += 1
            assert n < 100000, "the socket never filled"
        batch = []
        slow.stream._push_finish("length", batch)
        fast.stream._push_finish("length", batch)
        writer.write(batch)
        fast.read_to_done()
        fast.handler.join(30)
        assert not fast.handler.is_alive() and slow.handler.is_alive()
        assert writer._thread.is_alive()        # the drain, for the slow one
        got = slow.read_to_done()               # now it reads: all of it,
        assert _token_ids(got) == list(range(n))    # in order, each once
        slow.handler.join(30)
        assert not slow.handler.is_alive()
        slow.close(), fast.close()

    def test_a_client_that_went_away_cancels(self, writer):
        wire = _Wire(writer)
        wire.handler.start()
        wait_until(lambda: wire.stream._sink is not None, "the registration")
        wire.client.close()
        for t in range(3):      # the reset surfaces at a send
            wire.push(("token", t))
        wire.handler.join(30)
        assert not wire.handler.is_alive()
        assert wire.stream._cancel
        assert wire.stream.gateway._wake.is_set()
        assert not writer._sinks
        wire.close()


class TestStreamWriterStress:
    def test_registrations_race_pushes_and_nothing_is_lost(self, writer):
        """More drivers and handlers than cores, the interpreter switching
        every few bytecodes: every stream's registration races its
        driver's pushes, and each client still reads every token once, in
        order, and the counters add up."""
        import sys
        n_streams, n_tokens, n_drivers = 24, 200, 6
        wires = [_Wire(writer, f"cmpl-{i}") for i in range(n_streams)]
        got = [None] * n_streams

        def drive(mine):
            for t in range(n_tokens):
                batch = []
                for w in mine:
                    w.stream._push_token(t, batch)
                if batch:
                    writer.write(batch)
            batch = []
            for w in mine:
                w.stream._push_finish("length", batch)
            if batch:
                writer.write(batch)

        def read(i):
            got[i] = wires[i].read_to_done()

        threads = [threading.Thread(target=drive, args=(wires[k::n_drivers],))
                   for k in range(n_drivers)]
        threads += [threading.Thread(target=read, args=(i,))
                    for i in range(n_streams)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for th in threads:
                th.start()
            for w in wires:         # while the drivers push
                w.handler.start()
            for th in threads:
                th.join(120)
            assert not any(th.is_alive() for th in threads)
        finally:
            sys.setswitchinterval(interval)
        for data in got:
            assert _token_ids(data) == list(range(n_tokens))
            usage = json.loads(
                data.split(b"\n\n")[-3][len(b"data: "):])["usage"]
            assert usage["completion_tokens"] == n_tokens
        assert writer._m_events.value() == n_streams * (n_tokens + 1)
        for w in wires:
            w.handler.join(30)
            assert not w.handler.is_alive()
            w.close()
        assert not writer._sinks


def _open_sse(server, payload, rcvbuf=None):
    """A raw streaming request: the socket once the response's headers are
    read, and what of the body came with them."""
    s = socket.socket()
    if rcvbuf is not None:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, rcvbuf)
    s.settimeout(60)
    s.connect((server.host, server.port))
    body = json.dumps(dict(payload, stream=True)).encode()
    s.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: x\r\n"
              b"Content-Type: application/json\r\nContent-Length: "
              + str(len(body)).encode() + b"\r\n\r\n" + body)
    head = b""
    while b"\r\n\r\n" not in head:
        got = s.recv(1)
        assert got, head
        head += got
    assert head.startswith(b"HTTP/1.1 200")
    return s


def _read_events(sock):
    """The rest of a streamed body to its end: the events' JSON documents,
    ``[DONE]`` as it is."""
    data = b""
    while True:
        got = sock.recv(65536)
        if not got:
            break
        data += got
    frames = data.split(b"\n\n")
    assert frames[-1] == b""
    out = []
    for f in frames[:-1]:
        assert f.startswith(b"data: "), f
        text = f[len(b"data: "):].decode()
        out.append(text if text == "[DONE]" else json.loads(text))
    return out


def _metric(server, name):
    with urllib.request.urlopen(server.url + "/metrics", timeout=30) as r:
        fams = parse_prometheus(r.read().decode())
    return sum(v for fam in fams.values()
               for (sample, _), v in fam["samples"].items()
               if sample == name)


class TestStreamWriterServed:
    def test_a_step_is_one_hand_over_for_all_streams(self, model, server):
        """N streams decoding together: events over batches is about N, and
        each client saw one event a token, in order, then the finish with
        its usage and [DONE]."""
        reqs = [{"prompt": _prompt(40 + i), "max_tokens": 100}
                for i in range(NUM_SLOTS)]
        want = [_direct(model, GenerationRequest(
            prompt=r["prompt"], max_new_tokens=100))[0] for r in reqs]
        b0 = _metric(server, "serving_stream_batches_total")
        e0 = _metric(server, "serving_stream_events_total")
        socks = [_open_sse(server, r) for r in reqs]
        got = [None] * len(socks)

        def read(i):
            got[i] = _read_events(socks[i])

        ths = [threading.Thread(target=read, args=(i,))
               for i in range(len(socks))]
        for th in ths:
            th.start()
        for th in ths:
            th.join(120)
        for events, ids in zip(got, want):
            assert [e["choices"][0]["token_id"] for e in events[:-2]] == ids
            assert events[-2]["choices"][0]["finish_reason"] == "length"
            assert events[-2]["usage"] == {
                "prompt_tokens": 8, "completion_tokens": 100,
                "total_tokens": 108}
            assert events[-1] == "[DONE]"
        batches = _metric(server, "serving_stream_batches_total") - b0
        events = _metric(server, "serving_stream_events_total") - e0
        assert events == NUM_SLOTS * 101
        # the second request is admitted a few steps behind the first
        assert events / batches > 0.8 * NUM_SLOTS
        assert _metric(server, "serving_stream_backlogged_total") == 0
        for s in socks:
            s.close()

    def test_a_stalled_reader_stalls_nobody(self, model):
        """A reader that never reads: the driver steps on, the other stream
        is served whole, the stalled one's events are counted as
        backlogged, and its close frees the slot and the handler."""
        eng = _engine(model)
        gw = ServingGateway(eng, max_queue=8)
        # a long model name makes a frame 2 KB, and an accepted socket
        # inherits the listener's send buffer: a few events fill it
        srv = ServingHTTPServer(gw, port=0, model_name="m" * 2048)
        srv._httpd.socket.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                     4096)
        srv.start()
        try:
            free0 = eng.cache.num_free
            stalled = _open_sse(srv, {"prompt": _prompt(50),
                                      "max_tokens": 110}, rcvbuf=2048)
            wait_until(lambda: _metric(
                srv, "serving_stream_backlogged_total") > 0, "a backlog")
            steps0 = _metric(srv, "serving_step_duration_seconds_count")
            req = GenerationRequest(prompt=_prompt(51), max_new_tokens=30)
            toks, reason, _ = _sse(srv, {"prompt": _prompt(51),
                                         "max_tokens": 30})
            assert (toks, reason) == _direct(model, req)
            assert _metric(
                srv, "serving_step_duration_seconds_count") > steps0
            assert len(srv.stream_writer._sinks) == 1   # still held
            stalled.close()     # unread data: the server's socket is reset
            wait_until(lambda: not srv.stream_writer._sinks,
                       "the stalled stream's sink dropped")
            wait_until(lambda: eng.cache.num_free == free0, "the slot back")
        finally:
            srv.shutdown(drain=False, timeout=30)

    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_the_driver_dying_ends_every_stream_with_the_error_event(
            self, model):
        """``_run``'s last act reaches the registered streams through the
        writer: a terminal error event and [DONE], never a dropped
        connection."""
        from paddle_tpu.serving.faults import FaultPlan
        gw = ServingGateway(_engine(model), max_queue=8,
                            fault_hook=FaultPlan().at_step(6, "fatal"))
        srv = ServingHTTPServer(gw, port=0, model_name="dying").start()
        try:
            socks = [_open_sse(srv, {"prompt": _prompt(55 + i),
                                     "max_tokens": 100})
                     for i in range(NUM_SLOTS)]
            for s in socks:
                events = _read_events(s)
                assert events[-1] == "[DONE]"
                last = events[-2]
                assert last["choices"][0]["finish_reason"] == "error"
                assert last["error"]["message"].startswith(
                    "engine driver died")
                assert last["error"]["type"] == "server_error"
                assert all(e["choices"][0]["token_id"] is not None
                           for e in events[:-2])
                s.close()
        finally:
            srv.shutdown(drain=False, timeout=30)

    @pytest.mark.parametrize("drain", [True, False],
                             ids=["drain", "cancel"])
    def test_shutdown_returns_after_the_last_frame(self, model, drain):
        """When ``shutdown`` returns, an in-flight stream's last frame is
        on the wire: the writer holds no stream, and the client reads a
        whole response from a server that does nothing more."""
        srv = serve(model, port=0, num_slots=NUM_SLOTS, max_seq_len=S_MAX,
                    max_queue=8, model_name="drain-sse")
        sock = _open_sse(srv, {"prompt": _prompt(58), "max_tokens": 60})
        wait_until(lambda: srv.gateway.engine.num_active > 0, "admission")
        srv.shutdown(drain=drain, timeout=60)
        assert not srv.stream_writer._sinks
        events = _read_events(sock)
        assert events[-1] == "[DONE]"
        n = len(events) - 2
        reason = events[-2]["choices"][0]["finish_reason"]
        assert events[-2]["usage"]["completion_tokens"] == n
        if drain:
            want, _ = _direct(model, GenerationRequest(
                prompt=_prompt(58), max_new_tokens=60))
            assert [e["choices"][0]["token_id"]
                    for e in events[:-2]] == want
            assert reason == "length"
        else:
            assert reason in ("cancelled", "length") and n <= 60
        sock.close()
