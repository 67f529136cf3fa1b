"""Prometheus text-exposition helpers (profiler/metrics.py): the
counter/gauge/histogram layer the serving gateway's ``GET /metrics``
renders through. The parser here is intentionally strict about the
v0.0.4 text format — the same parser validates live scrapes in
tests/test_serving_server.py."""
import math
import re
import threading

import pytest

from paddle_tpu.profiler.metrics import (Counter, Gauge, Histogram,
                                         MetricsRegistry)

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^}]*)\})? '
    r'(?P<value>[^ ]+)$')
_LABEL_RE = re.compile(r'^(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>.*)"$')


def parse_prometheus(text):
    """Parse exposition text -> {family: {"type", "help", "samples"}}
    with samples as {(name, label_items): float}. Raises AssertionError
    on any format violation (samples before TYPE, bad label syntax,
    non-float values, missing trailing newline)."""
    assert text.endswith("\n"), "exposition must end with a newline"
    fams, cur = {}, None
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            name, _, help_ = rest.partition(" ")
            fams.setdefault(name, {"help": help_, "samples": {}})
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram", "summary")
            fams.setdefault(name, {"samples": {}})["type"] = kind
            cur = name
            continue
        assert not line.startswith("#"), f"unknown comment: {line}"
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name = m.group("name")
        labels = []
        if m.group("labels"):
            for pair in re.findall(r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|'
                                   r'\\.)*"', m.group("labels")):
                lm = _LABEL_RE.match(pair)
                assert lm, f"malformed label: {pair!r}"
                labels.append((lm.group("k"), lm.group("v")))
        v = m.group("value")
        value = math.inf if v == "+Inf" else \
            -math.inf if v == "-Inf" else float(v)
        # samples must belong to the most recent TYPE'd family
        assert cur is not None and name.startswith(cur), \
            f"sample {name} outside its family block (cur={cur})"
        fams[cur]["samples"][(name, tuple(labels))] = value
    return fams


class TestCounter:
    def test_inc_and_expose(self):
        c = Counter("requests_total", "Total requests.")
        c.inc()
        c.inc(4)
        text = "\n".join(c.expose()) + "\n"
        fams = parse_prometheus(text)
        assert fams["requests_total"]["type"] == "counter"
        assert fams["requests_total"]["samples"][
            ("requests_total", ())] == 5

    def test_labels_sorted_and_separate(self):
        c = Counter("finished_total")
        c.inc(reason="stop")
        c.inc(reason="timeout")
        c.inc(2, reason="stop")
        s = parse_prometheus("\n".join(c.expose()) + "\n")[
            "finished_total"]["samples"]
        assert s[("finished_total", (("reason", "stop"),))] == 3
        assert s[("finished_total", (("reason", "timeout"),))] == 1

    def test_decrease_rejected(self):
        c = Counter("n")
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)


class TestGauge:
    def test_set_inc_dec(self):
        g = Gauge("queue_depth")
        g.set(7)
        g.inc()
        g.dec(3)
        assert g.value() == 5

    def test_scrape_time_callable(self):
        """set_fn gauges sample at render time — the gateway points
        these at engine state so a scrape can never be stale."""
        depth = [3]
        g = Gauge("active_slots")
        g.set_fn(lambda: depth[0])
        assert "active_slots 3" in g.expose()
        depth[0] = 9
        assert "active_slots 9" in g.expose()


class TestHistogram:
    def test_buckets_cumulative_sum_count(self):
        h = Histogram("latency_seconds", "Request latency.",
                      buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        fams = parse_prometheus("\n".join(h.expose()) + "\n")
        s = fams["latency_seconds"]["samples"]
        assert fams["latency_seconds"]["type"] == "histogram"

        def bucket(le):
            return s[("latency_seconds_bucket", (("le", le),))]

        assert bucket("0.1") == 1
        assert bucket("1") == 3      # cumulative, not per-bin
        assert bucket("10") == 4
        assert bucket("+Inf") == 5
        assert s[("latency_seconds_count", ())] == 5
        assert s[("latency_seconds_sum", ())] == pytest.approx(56.05)

    def test_bucket_monotonicity_invariant(self):
        h = Histogram("x", buckets=(1, 2, 4, 8))
        import random
        rng = random.Random(3)
        for _ in range(200):
            h.observe(rng.uniform(0, 10))
        s = parse_prometheus("\n".join(h.expose()) + "\n")["x"]["samples"]
        buckets = {float(lab[0][1].replace("+Inf", "inf")): v
                   for (name, lab), v in s.items() if name == "x_bucket"}
        counts = [buckets[le] for le in sorted(buckets)]
        assert counts == sorted(counts)  # cumulative ⇒ non-decreasing
        assert counts[-1] == 200

    def test_quantile_interpolates_within_bucket(self):
        """quantile(): histogram_quantile-style linear interpolation —
        exact at bucket boundaries, proportional inside, clamped to the
        last finite bound past it, 0 on an empty series."""
        h = Histogram("q", buckets=(1.0, 2.0, 4.0))
        assert h.quantile(0.5) == 0.0            # empty
        for v in (0.5, 1.5, 1.5, 3.0):           # counts: 1, 3, 4
            h.observe(v)
        # rank 2 of 4 lands in (1, 2]: prev_count 1, bucket count 3
        assert h.quantile(0.5) == pytest.approx(1.0 + (2 - 1) / (3 - 1))
        # target rank == a bucket's cumulative count -> its upper bound
        assert h.quantile(0.25) == pytest.approx(1.0)
        # fractional rank inside the first bucket interpolates from 0
        assert h.quantile(0.125) == pytest.approx(0.5)
        assert h.quantile(1.0) == pytest.approx(4.0)
        h.observe(100.0)                         # beyond the ladder
        assert h.quantile(0.99) == 4.0           # clamps to last bound
        with pytest.raises(ValueError, match="quantile"):
            h.quantile(0.0)
        with pytest.raises(ValueError, match="quantile"):
            h.quantile(1.5)

    def test_ttft_ladder_resolves_sub_ms(self):
        """The serving_ttft_seconds ladder (TTFT_BUCKETS) keeps sub-ms
        resolution at the low end and spans to 30s — the p95 of a
        tight sub-ms population must not collapse into one giant
        default bucket."""
        from paddle_tpu.profiler.metrics import (DEFAULT_BUCKETS,
                                                 TTFT_BUCKETS)
        assert TTFT_BUCKETS[0] < DEFAULT_BUCKETS[0]
        h = Histogram("ttft", buckets=TTFT_BUCKETS)
        for _ in range(100):
            h.observe(0.0008)
        assert h.quantile(0.95) <= 0.001   # resolved, not smeared to 5ms

    def test_step_ladder_strict_parsed_and_resolves_fast_steps(self):
        """The serving_step_duration_seconds ladder (STEP_BUCKETS) —
        the same signal the engine's headroom-adaptive chunk budget
        reads — resolves sub-ms on-chip steps AND tens-of-ms CPU steps,
        and a histogram on it renders valid under the strict parser."""
        from paddle_tpu.profiler.metrics import (MetricsRegistry,
                                                 STEP_BUCKETS)
        assert STEP_BUCKETS[0] <= 0.0005       # real-chip step floor
        assert STEP_BUCKETS[-1] >= 10.0        # wedged-step ceiling
        assert list(STEP_BUCKETS) == sorted(STEP_BUCKETS)
        r = MetricsRegistry()
        h = r.histogram("serving_step_duration_seconds",
                        "Engine step() wall duration.",
                        buckets=STEP_BUCKETS)
        for v in (0.0003, 0.02, 0.02, 1.5):
            h.observe(v)
        fams = parse_prometheus(r.render())
        name = "serving_step_duration_seconds"
        assert fams[name]["type"] == "histogram"
        assert fams[name]["samples"][(name + "_count", ())] == 4
        bounds = {lbl[1] for key, lbls in fams[name]["samples"]
                  if key == name + "_bucket" for lbl in lbls
                  if lbl[0] == "le"}
        assert len(bounds) == len(STEP_BUCKETS) + 1   # ladder + +Inf
        # CPU steps land mid-ladder, not smeared into +Inf
        assert h.quantile(0.5) <= 0.025

    def test_spec_accept_ladder_strict_parsed_integer_resolved(self):
        """The serving_spec_accept_length ladder (SPEC_ACCEPT_BUCKETS)
        — tokens emitted per speculative verify span — gives every
        practical acceptance count (1 .. spec_k+1 for spec_k <= 5) its
        own bucket, and a histogram on it renders valid under the
        strict parser. The engine-level drain into this histogram is
        pinned in tests/test_spec_decode.py."""
        from paddle_tpu.profiler.metrics import (SPEC_ACCEPT_BUCKETS,
                                                 MetricsRegistry)
        assert SPEC_ACCEPT_BUCKETS[0] == 1.0   # nothing-accepted floor
        assert list(SPEC_ACCEPT_BUCKETS) == sorted(SPEC_ACCEPT_BUCKETS)
        assert set(SPEC_ACCEPT_BUCKETS[:6]) == {1, 2, 3, 4, 5, 6}
        r = MetricsRegistry()
        h = r.histogram("serving_spec_accept_length",
                        "Tokens emitted per verify span.",
                        buckets=SPEC_ACCEPT_BUCKETS)
        for v in (1, 1, 4, 2):
            h.observe(v)
        fams = parse_prometheus(r.render())
        name = "serving_spec_accept_length"
        assert fams[name]["type"] == "histogram"
        assert fams[name]["samples"][(name + "_count", ())] == 4
        assert fams[name]["samples"][(name + "_sum", ())] == 8
        bounds = {lbl[1] for key, lbls in fams[name]["samples"]
                  if key == name + "_bucket" for lbl in lbls
                  if lbl[0] == "le"}
        assert len(bounds) == len(SPEC_ACCEPT_BUCKETS) + 1
        # integer counts resolve exactly: the le="1" bucket holds only
        # the nothing-accepted spans
        assert fams[name]["samples"][
            (name + "_bucket", (("le", "1"),))] == 2

    def test_empty_buckets_rejected(self):
        with pytest.raises(ValueError, match="at least one bucket"):
            Histogram("x", buckets=())


class TestRegistry:
    def test_render_whole_registry(self):
        reg = MetricsRegistry()
        reg.counter("a_total", "A.").inc(2)
        reg.gauge("b", "B.").set(1.5)
        reg.histogram("c_seconds", buckets=(1.0,)).observe(0.5)
        fams = parse_prometheus(reg.render())
        assert set(fams) == {"a_total", "b", "c_seconds"}
        assert fams["b"]["samples"][("b", ())] == 1.5

    def test_reregister_returns_same_instance(self):
        reg = MetricsRegistry()
        c1 = reg.counter("x_total")
        c2 = reg.counter("x_total")
        assert c1 is c2
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")

    def test_thread_safety_counts_exact(self):
        """8 threads x 1000 incs: the registry lock discipline loses
        nothing (the gateway's driver + HTTP threads hit this path)."""
        reg = MetricsRegistry()
        c = reg.counter("hits_total")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value() == 8000


# ------------------------------------------- the host's stalls on /metrics
class TestHostStallFamilies:
    def test_the_four_families_strict_parse_with_every_series(self):
        """A gateway that has not run a step already exposes a series a
        phase of the long visits and a series a generation of the
        collector's pauses, all at 0 (ISSUE 52)."""
        import serving_support
        from paddle_tpu.profiler.driver_clock import PHASES
        from paddle_tpu.serving.server.gateway import ServingGateway
        gw = ServingGateway(
            serving_support.engine(serving_support.model("llama", seed=35)),
            start=False)
        fams = parse_prometheus(gw.registry.render())
        for name, label, values in (
                ("serving_driver_long_visits_total", "phase", PHASES),
                ("serving_driver_long_visit_seconds_total", "phase", PHASES),
                ("serving_gc_pause_seconds_total", "generation", "012"),
                ("serving_gc_collections_total", "generation", "012")):
            fam = fams[name]
            assert fam["type"] == "counter" and fam["help"]
            assert sorted(dict(labels)[label] for _, labels
                          in fam["samples"]) == sorted(values), name
            assert set(fam["samples"].values()) == {0.0}, name
        assert len(PHASES) == 10
