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
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.serving import GenerationRequest
from paddle_tpu.serving.server import (QueueFullError, ServingGateway,
                                       ServingHTTPServer, serve)

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
