"""Multi-tenant SLO serving (serving/policy/, README "Multi-tenant SLO
serving"): priority classes, deadline-aware admission, SLO-driven
preemption, and the class-headroom fleet signals.

The acceptance matrix:

- the CLASS TABLE parses the CLI spec (ranks descend with position,
  ``*`` default marker, ``:k`` reserved headroom, aligned ms target
  lists), resolves unknown names to a ValueError (the HTTP 400, never
  a driver crash), and the default single-class table is INACTIVE —
  the engine keeps the plain FIFO scheduler and every banked baseline
  stays byte-identical;
- ADMISSION order under the PolicyScheduler is (effective class rank,
  TTFT deadline slack, FIFO tick), deterministic under a VirtualClock;
  within one class it collapses to exact FIFO; anti-starvation aging
  promotes a long-waiting batch request one rank per quantum;
- HEADROOM: reserved slots are held back from other classes, and the
  reserving class admits into its own reservation first;
- PREEMPTION: an SLO-urgent latency request displaces running
  best-effort work through the ordinary preemption-by-recompute path
  — victim streams BYTE-IDENTICAL after restore (greedy AND seeded),
  ``decode_compilations() == 1`` throughout, equals never displace
  equals, and a fixed virtual-time schedule replays identically;
- the /metrics surface gains ``class``-labeled latency series plus the
  ``serving_slo_misses_total`` / ``serving_policy_preemptions_total``
  counters ONLY when a table is active (policy-off scrapes keep their
  exact label shape);
- fleet: ``class_pressure`` ranks preemptible-load replicas first and
  the ``class-headroom`` router stays pure/deterministic.
"""
import json
import urllib.error
import urllib.request

import pytest

from paddle_tpu.serving import (ClassTable, FIFOScheduler, GenerationRequest,
                                PolicyScheduler, PriorityClass,
                                VirtualClock)
from paddle_tpu.serving.policy import select_victims, victim_key
from paddle_tpu.serving.server import serve

import serving_support
from serving_support import (BS, CHUNK, S_MAX, SLOTS, prompt as _prompt,
                             wait_until)
from test_metrics_prom import parse_prometheus

#: the canonical three-way split the README documents
SPEC = dict(classes="latency:1,standard,batch*",
            slo_ttft_ms="80,400,0", slo_tpot_ms="50,0,0")
#: same tiers, no reserved headroom — the engine preemption tests want
#: batch work to be ABLE to fill every slot first
SPEC_NO_RESERVE = dict(SPEC, classes="latency,standard,batch*")


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=33)  # GQA, pallas decode


def _req(ps, n=12, **kw):
    kw.setdefault("max_new_tokens", 8)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


def _clone(r, drop_class=False):
    return GenerationRequest(
        prompt=r.prompt, max_new_tokens=r.max_new_tokens,
        temperature=r.temperature, top_k=r.top_k,
        eos_token_id=r.eos_token_id, seed=r.seed,
        priority_class=None if drop_class else r.priority_class)


def _engine(model, **kw):
    """The shared helper, with a trie unless told otherwise."""
    kw.setdefault("prefix_cache", True)
    return serving_support.engine(model, **kw)


def _baseline(model, reqs, **kw):
    """Policy-off single-class oracle streams for the same requests."""
    eng = _engine(model, **kw)
    return [o.tolist() for o in
            eng.generate([_clone(r, drop_class=True) for r in reqs])]


def _drive(eng, clk, dt=0.001):
    while eng.has_work():
        eng.step()
        clk.advance(dt)


# ------------------------------------------------------- class table units
class TestClassTable:
    def test_parse_canonical_three_way_spec(self):
        t = ClassTable.parse(**SPEC)
        assert [c.name for c in t] == ["latency", "standard", "batch"]
        assert [c.rank for c in t] == [2, 1, 0]     # descend with position
        lat, std, bat = t.classes
        assert lat.reserved_slots == 1 and std.reserved_slots == 0
        assert lat.ttft_slo_s == pytest.approx(0.08)
        assert std.ttft_slo_s == pytest.approx(0.4)
        assert bat.ttft_slo_s is None               # 0 = no target
        assert lat.tpot_slo_s == pytest.approx(0.05)
        assert t.default == "batch"                 # the '*' marker
        assert t.active
        rows = t.doc()                              # the banner surface
        assert rows[0]["ttft_slo_ms"] == 80 and rows[0]["rank"] == 2
        assert [r["default"] for r in rows] == [False, False, True]

    def test_parse_default_falls_to_last_and_rejects_bad_specs(self):
        assert ClassTable.parse("gold,best-effort").default == "best-effort"
        with pytest.raises(ValueError, match="two defaults"):
            ClassTable.parse("a*,b*")
        with pytest.raises(ValueError, match="bad class name"):
            ClassTable.parse("a,!b")
        with pytest.raises(ValueError, match="duplicate"):
            ClassTable.parse("a,a")
        with pytest.raises(ValueError, match="targets"):
            ClassTable.parse("a,b", slo_ttft_ms="1,2,3")
        with pytest.raises(ValueError, match=">= 0"):
            ClassTable.parse("a,b", slo_ttft_ms="-5")

    def test_resolve_unknown_is_the_400_valueerror(self):
        t = ClassTable.parse(**SPEC)
        assert t.resolve(None).name == "batch"      # unlabeled -> default
        assert t.resolve("latency").rank == 2
        with pytest.raises(ValueError, match="unknown priority_class"):
            t.resolve("gold")
        with pytest.raises(ValueError, match="batch.*latency.*standard"):
            t.resolve("gold")                       # names the closed set

    def test_neutral_single_table_is_inactive(self):
        """The byte-identity gate: no knobs -> no policy scheduler."""
        assert not ClassTable.single().active
        assert not ClassTable.coerce(None).active
        assert not ClassTable.parse("standard").active
        # any target, reservation, or second class flips it on
        assert ClassTable.parse("standard", slo_ttft_ms="100").active
        assert ClassTable.parse("standard:1").active
        assert ClassTable.parse("a,b").active


# ----------------------------------------------------- victim choice units
class _Slot:
    """Victim-facing stand-in for a running sequence."""

    def __init__(self, rid, rank, t_admitted, ntok, done=False):
        self.request_id = rid
        self.pclass = PriorityClass(f"c{rank}", rank=rank)
        self.t_admitted = t_admitted
        self.tokens = [0] * ntok
        self.done = done


class TestVictimSelection:
    def test_lowest_class_then_most_recent_then_least_work(self):
        slots = [
            _Slot(1, rank=1, t_admitted=1.0, ntok=2),   # higher class
            _Slot(2, rank=0, t_admitted=5.0, ntok=9),   # recent, much work
            _Slot(3, rank=0, t_admitted=9.0, ntok=4),   # most recent
            _Slot(4, rank=0, t_admitted=9.0, ntok=2),   # tie: least lost
            None,
            _Slot(5, rank=0, t_admitted=99.0, ntok=0, done=True),
        ]
        got = select_victims(slots, 3, below_rank=2)
        assert [s.request_id for s in got] == [4, 3, 2]  # never 1 first
        # strictly-below filter: rank 1 work is untouchable at rank 1
        assert select_victims(slots, 1, below_rank=1)[0].request_id == 4
        assert select_victims(slots, 9, below_rank=0) == []

    def test_victim_key_total_order_is_deterministic(self):
        a = _Slot(7, rank=0, t_admitted=3.0, ntok=5)
        b = _Slot(8, rank=0, t_admitted=3.0, ntok=5)
        assert victim_key(a) != victim_key(b)   # request_id tiebreak
        assert sorted([b, a], key=victim_key)[0].request_id == 8


# ------------------------------------------------- policy scheduler units
class _Q:
    """Scheduler-facing stand-in for a queued sequence."""

    _next_id = 0

    def __init__(self, pclass, t_submit, work_len=12):
        _Q._next_id += 1
        self.request_id = _Q._next_id
        self.pclass = pclass
        self.t_submit = t_submit
        self.work_len = work_len
        self.prefix_hit_tokens = 0
        self.done = False


def _sched(table, clk, **kw):
    return PolicyScheduler(decode_chunk=1, table=table, clock=clk, **kw)


class TestPolicyScheduler:
    def test_admission_orders_by_class_then_slack_then_fifo(self):
        t = ClassTable.parse(**dict(SPEC, classes="latency,standard,batch*"))
        clk = VirtualClock()
        s = _sched(t, clk)
        lat, std, bat = t.classes
        old_std = _Q(std, t_submit=0.0)     # waited longest: least slack
        new_std = _Q(std, t_submit=0.2)
        b1, b2 = _Q(bat, t_submit=0.0), _Q(bat, t_submit=0.1)
        late_lat = _Q(lat, t_submit=0.3)    # newest, highest class
        for q in (b1, b2, old_std, new_std, late_lat):
            s.submit(q)
        clk.advance(0.35)
        got = s.admissions(5)
        # class rank first; slack orders within standard; batch (no
        # target, equal inf slack) keeps exact FIFO by queue_tick
        assert got == [late_lat, old_std, new_std, b1, b2]
        assert s.num_queued == 0

    def test_single_class_collapses_to_exact_fifo(self):
        """Neutral table + PolicyScheduler == FIFOScheduler order (the
        scheduler-level half of the byte-identity story)."""
        clk = VirtualClock()
        s = _sched(ClassTable.single(), clk)
        f = FIFOScheduler(decode_chunk=1)
        std = ClassTable.single().classes[0]
        qs = [_Q(std, t_submit=0.01 * i) for i in range(6)]
        for q in qs:
            s.submit(q)
            f.submit(q)
        clk.advance(1.0)
        assert s.admissions(4) == f.admissions(4)
        assert s.admissions(4) == f.admissions(4)

    def test_aging_promotes_starved_batch_one_rank_per_quantum(self):
        """A steady latency arrival stream never permanently starves
        batch: each full aging quantum waited raises the EFFECTIVE
        admission rank by one, and two quanta outrank a fresh latency
        request outright."""
        t = ClassTable.parse("latency,batch*", slo_ttft_ms="500,0",
                             aging_s=10.0)
        clk = VirtualClock()
        s = _sched(t, clk)
        lat, bat = t.classes
        starved = _Q(bat, t_submit=0.0)
        s.submit(starved)
        s.submit(_Q(lat, t_submit=0.0))
        clk.advance(5.0)        # < one quantum: class order holds
        assert s.effective_rank(starved, clk()) == 0
        assert [q.pclass.name for q in s.admissions(1)] == ["latency"]
        s.submit(_Q(lat, t_submit=clk()))
        clk.advance(7.0)        # starved waited 12s = one quantum
        assert s.effective_rank(starved, clk()) == 1
        # equal effective rank: slack decides — the fresh latency
        # request's 500ms target is blown (negative slack beats inf)
        assert [q.pclass.name for q in s.admissions(1)] == ["latency"]
        s.submit(_Q(lat, t_submit=clk()))
        clk.advance(9.0)        # starved at 21s = two quanta; the
        assert s.effective_rank(starved, clk()) == 2    # fresh one at 0
        assert s.admissions(1) == [starved]     # batch finally drains

    def test_reserved_headroom_holds_slots_for_the_reserving_class(self):
        t = ClassTable.parse("latency:1,batch*")
        clk = VirtualClock()
        running = {"latency": 0}
        s = _sched(t, clk, slot_usage=lambda: dict(running))
        lat, bat = t.classes
        flood = [_Q(bat, t_submit=0.0) for _ in range(3)]
        for q in flood:
            s.submit(q)
        # 2 free slots, latency owed 1: the batch flood gets exactly 1
        assert s.admissions(2) == flood[:1]
        assert s.num_queued == 2
        # the reserving class admits INTO its reservation
        hot = _Q(lat, t_submit=0.0)
        s.submit(hot)
        got = s.admissions(1)
        assert got == [hot]
        # reservation satisfied by running work: batch flows again
        running["latency"] = 1
        assert s.admissions(2) == flood[1:]

    def test_urgent_names_only_ttft_classes_past_the_fraction(self):
        t = ClassTable.parse(**SPEC)
        clk = VirtualClock()
        s = _sched(t, clk)      # urgency_frac 0.5 default
        lat, std, bat = t.classes
        hot = _Q(lat, t_submit=0.0)
        warm = _Q(lat, t_submit=0.05)
        never = _Q(bat, t_submit=0.0)   # no TTFT target: never urgent
        for q in (hot, warm, never):
            s.submit(q)
        clk.advance(0.041)      # hot waited 41ms >= 80*0.5; warm hasn't
        assert s.urgent() == [hot]
        clk.advance(0.05)
        assert s.urgent() == [hot, warm]
        with pytest.raises(ValueError, match="urgency_frac"):
            _sched(t, clk, urgency_frac=0.0)

    def test_queue_object_identity_survives_admission(self):
        """The gateway snapshots ``scheduler.queue`` — the policy
        scheduler must mutate it in place, never rebind it."""
        t = ClassTable.parse("a,b*")
        s = _sched(t, VirtualClock())
        q0 = s.queue
        for q in [_Q(t.classes[1], 0.0) for _ in range(3)]:
            s.submit(q)
        s.admissions(2)
        assert s.queue is q0 and len(s.queue) == 1


# -------------------------------------------------- engine-level behavior
class TestEnginePolicy:
    def test_default_engine_keeps_fifo_and_streams_byte_identical(self, model):
        """No policy knobs (or an inactive single-class spec) -> the
        plain FIFOScheduler, no policy counters moving, and tokens
        byte-identical to the baseline."""
        reqs = [_req(1), _req(2, temperature=0.9, top_k=5, seed=123)]
        want = _baseline(model, reqs)
        eng = _engine(model, priority_classes="standard")
        assert type(eng.scheduler) is FIFOScheduler
        assert not eng.classes.active
        got = [o.tolist() for o in eng.generate([_clone(r) for r in reqs])]
        assert got == want
        assert eng.stats["policy_preemptions"] == 0

    def test_labeled_requests_resolve_and_unknown_is_valueerror(self, model):
        eng = _engine(model, priority_classes=ClassTable.parse(**SPEC))
        assert isinstance(eng.scheduler, PolicyScheduler)
        seq = eng.submit(_req(3, priority_class="latency"))
        assert seq.pclass.name == "latency" and seq.pclass.rank == 2
        unlabeled = eng.submit(_req(4))
        assert unlabeled.pclass.name == "batch"     # the '*' default
        with pytest.raises(ValueError, match="unknown priority_class"):
            eng.submit(_req(5, priority_class="gold"))
        _drive(eng, VirtualClock())

    def test_slo_urgent_latency_preempts_batch_byte_identically(self, model):
        """THE tentpole pin: a latency request that burns past half its
        TTFT budget displaces running batch work by recompute; all
        three streams — greedy batch, SEEDED batch, latency — finish
        byte-identical to their policy-off baselines, and the whole
        episode adds zero decode traces."""
        clk = VirtualClock()
        reqs = [_req(6, max_new_tokens=16, priority_class="batch"),
                _req(7, max_new_tokens=16, temperature=0.9, top_k=5,
                     seed=123, priority_class="batch"),
                _req(8, n=8, max_new_tokens=4, priority_class="latency")]
        want = [_baseline(model, [r])[0] for r in reqs]
        eng = _engine(model, step_clock=clk,
                      priority_classes=ClassTable.parse(**SPEC_NO_RESERVE))
        traced = eng.decode_compilations()  # by the baselines, just above
        b1, b2 = eng.submit(_clone(reqs[0])), eng.submit(_clone(reqs[1]))
        for _ in range(3):          # both batch rows running mid-decode
            eng.step()
            clk.advance(0.001)
        assert b1.status == "running" and b2.status == "running"
        lat = eng.submit(_clone(reqs[2]))
        assert eng.stats["policy_preemptions"] == 0
        clk.advance(0.05)           # 50ms >= 80ms * 0.5: urgent now
        eng.step()
        assert eng.stats["policy_preemptions"] == 1
        assert lat.slot is not None     # admitted into the freed slot
        victims = [s for s in (b1, b2) if s.status == "queued"]
        assert len(victims) == 1        # exactly one displaced
        _drive(eng, clk)
        got = [s.tokens for s in (b1, b2, lat)]
        assert got == want              # byte-identical incl. the victim
        assert eng.stats["restores"] >= 1
        assert eng.decode_compilations() == traced  # the episode adds none
        assert eng.cache.num_free == eng.num_slots

    def test_equals_never_displace_equals(self, model):
        """Urgent latency work never preempts running latency work —
        it waits for a natural slot."""
        clk = VirtualClock()
        eng = _engine(model, step_clock=clk,
                      priority_classes=ClassTable.parse(**SPEC))
        hogs = [eng.submit(_req(10 + i, max_new_tokens=10,
                                priority_class="latency"))
                for i in range(SLOTS)]
        eng.step()
        clk.advance(0.001)
        waiter = eng.submit(_req(15, priority_class="latency"))
        clk.advance(1.0)            # far past the whole TTFT budget
        eng.step()
        assert eng.stats["policy_preemptions"] == 0
        assert all(h.status == "running" for h in hogs)
        _drive(eng, clk)
        assert waiter.finish_reason == "length"

    def test_mixed_class_chaos_matrix_replays_deterministically(self, model):
        """A fixed virtual-time schedule of mixed-class traffic (bursts,
        preemptions, aging in play) loses ZERO requests and produces
        IDENTICAL streams, admission orders, and preemption counts on
        every replay."""
        def run():
            clk = VirtualClock()
            eng = _engine(model, step_clock=clk,
                          priority_classes=ClassTable.parse(
                              **SPEC_NO_RESERVE))
            seqs = [eng.submit(_req(20 + i, max_new_tokens=12,
                                    priority_class="batch"))
                    for i in range(3)]
            for _ in range(2):
                eng.step()
                clk.advance(0.002)
            seqs.append(eng.submit(_req(30, max_new_tokens=6,
                                        temperature=0.8, top_k=7, seed=11,
                                        priority_class="standard")))
            seqs.append(eng.submit(_req(31, n=8, max_new_tokens=4,
                                        priority_class="latency")))
            clk.advance(0.06)       # latency urgent, standard not yet
            for _ in range(4):
                eng.step()
                clk.advance(0.02)
            seqs.append(eng.submit(_req(32, n=8, max_new_tokens=4,
                                        priority_class="latency")))
            _drive(eng, clk, dt=0.02)
            return ([s.tokens for s in seqs],
                    [s.finish_reason for s in seqs],
                    eng.stats["policy_preemptions"], eng.stats["restores"])

        first, second = run(), run()
        assert first == second              # the replay pin
        toks, reasons, preempts, restores = first
        assert all(r in ("length", "stop") for r in reasons)  # 0 lost
        assert preempts >= 1 and restores >= preempts


# ------------------------------------------------------ HTTP + metrics
def _post(server, payload, headers=(), timeout=120):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        server.url + "/v1/completions", data=body,
        headers={"Content-Type": "application/json", **dict(headers)})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.load(r)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def _get(server, path, timeout=10):
    with urllib.request.urlopen(server.url + path, timeout=timeout) as r:
        return r.read().decode()


@pytest.fixture(scope="module")
def policy_server(model):
    srv = serve(model, port=0, num_slots=SLOTS, max_seq_len=S_MAX,
                max_queue=8, model_name="slo-test", **SPEC)
    yield srv
    srv.shutdown(drain=False, timeout=30)


class TestPolicyHTTP:
    def test_unknown_priority_class_is_a_400_not_a_crash(self, policy_server):
        status, doc = _post(policy_server, {
            "prompt": _prompt(40).tolist(), "max_tokens": 2,
            "priority_class": "gold"})
        assert status == 400
        assert doc["error"]["type"] == "invalid_request"
        assert "unknown priority_class" in doc["error"]["message"]
        # the engine is alive and still serving after the rejection
        status, doc = _post(policy_server, {
            "prompt": _prompt(40).tolist(), "max_tokens": 2})
        assert status == 200

    def test_body_field_and_header_both_select_the_class(self, policy_server):
        for extra in ({"priority_class": "latency"}, {}):
            headers = () if extra else \
                (("X-Priority-Class", "latency"),)
            status, doc = _post(policy_server, {
                "prompt": _prompt(41).tolist(), "max_tokens": 3, **extra},
                headers=headers)
            assert status == 200
            assert len(doc["choices"][0]["token_ids"]) == 3

    def test_metrics_scrape_carries_class_labels_and_policy_series(
            self, policy_server):
        _post(policy_server, {"prompt": _prompt(42).tolist(),
                              "max_tokens": 3, "priority_class": "latency"})
        fams = parse_prometheus(_get(policy_server, "/metrics"))
        # the new counters strict-parse, zero-seeded per class so the
        # series exist (and stay monotonic) before any miss/preemption
        miss = fams["serving_slo_misses_total"]
        assert miss["type"] == "counter"
        labels = {lab for (_, lab) in miss["samples"]}
        for cls in ("latency", "standard", "batch"):
            for slo in ("ttft", "tpot"):
                assert (("class", cls), ("slo", slo)) in labels
        pre = fams["serving_policy_preemptions_total"]["samples"]
        assert (("serving_policy_preemptions_total",
                 (("victim_class", "batch"),)) in pre)
        # the latency histograms carry the class label when policy is on
        ttft = fams["serving_ttft_seconds"]["samples"]
        assert any(name == "serving_ttft_seconds_count"
                   and ("class", "latency") in lab
                   for (name, lab) in ttft)

    def test_policy_off_scrape_keeps_the_unlabeled_shape(self, model):
        """The metrics back-compat gate: without a class table the
        histograms keep their EMPTY label tuples and the policy
        families are absent entirely."""
        srv = serve(model, port=0, num_slots=SLOTS, max_seq_len=S_MAX,
                    max_queue=8, model_name="plain")
        try:
            _post(srv, {"prompt": _prompt(43).tolist(), "max_tokens": 2})
            fams = parse_prometheus(_get(srv, "/metrics"))
            assert "serving_slo_misses_total" not in fams
            assert "serving_policy_preemptions_total" not in fams
            ttft = fams["serving_ttft_seconds"]["samples"]
            assert ttft[("serving_ttft_seconds_count", ())] > 0
        finally:
            srv.shutdown(drain=False, timeout=30)

    def test_debug_requests_gains_class_and_slack_columns(
            self, policy_server):
        gw = policy_server.gateway
        hogs = [gw.submit(_req(50 + i, max_new_tokens=40,
                               priority_class="batch"))
                for i in range(SLOTS)]
        waiter = gw.submit(_req(55, max_new_tokens=2,
                                priority_class="latency"))
        rows = []

        def listed():
            rows[:] = json.loads(_get(policy_server,
                                      "/debug/requests"))["requests"]
            return len(rows) >= 2
        wait_until(listed, "two requests in the table")
        by_class = {}
        for row in rows:
            assert "class" in row and "slo_slack_s" in row
            by_class.setdefault(row["class"], []).append(row)
        assert "batch" in by_class
        for row in by_class["batch"]:
            assert row["slo_slack_s"] is None       # no TTFT target
        for s in hogs + [waiter]:
            s.result()


# ------------------------------------------------------------ fleet units
class _StubReplica:
    """Router-facing stand-in with fixed load + class pressure."""

    def __init__(self, index, load, pressure):
        self.index = index
        self._load = load
        self._pressure = pressure
        self.routable = True
        self.alive = True

    def load(self):
        return self._load

    def class_pressure(self, request):
        return self._pressure


class TestClassHeadroomRouter:
    def test_ranks_by_pressure_then_load_then_index(self):
        from paddle_tpu.serving.fleet import (ClassHeadroomRouter,
                                              make_router)
        r = make_router("class-headroom")
        assert isinstance(r, ClassHeadroomRouter)
        # a busy-but-preemptible replica beats an idle-looking one
        # saturated with same-class work; ties fall to load, then index
        reps = [_StubReplica(0, load=9, pressure=4),
                _StubReplica(1, load=2, pressure=4),
                _StubReplica(2, load=50, pressure=0),
                _StubReplica(3, load=2, pressure=4)]
        order = r.rank(_req(60), reps)
        assert [x.index for x in order] == [2, 1, 3, 0]

    def test_fleet_replica_pressure_and_debug_row(self, model):
        """End-to-end replica signals: a replica whose slots hold batch
        work shows ZERO pressure to a latency request (all displaceable)
        and full pressure to a batch one; /debug/fleet rows gain the
        per-class occupancy + preemption columns only when policy is
        on."""
        from paddle_tpu.serving.fleet import EngineFleet
        fleet = EngineFleet(
            model, replicas=2, router="class-headroom", num_slots=SLOTS,
            max_seq_len=S_MAX, prefix_block_size=BS, prefill_chunk=CHUNK,
            max_queue=8, start=False, priority_classes=ClassTable.parse(
                **SPEC))
        try:
            assert fleet.classes.active
            rep = fleet.replicas[0]
            eng = rep.gateway.engine
            assert isinstance(eng.scheduler, PolicyScheduler)
            # table is shared fleet-wide, not re-parsed per replica
            assert all(r.gateway.engine.classes is fleet.classes
                       for r in fleet.replicas)
            b = eng.submit(_req(61, max_new_tokens=6,
                                priority_class="batch"))
            eng.step()
            assert rep.class_counts() == {"batch": 1}
            assert rep.class_pressure(_req(62, priority_class="latency")) == 0
            assert rep.class_pressure(_req(63, priority_class="batch")) == 1
            row = rep.row()
            assert row["classes"] == {"batch": 1}
            assert row["policy_preemptions"] == 0
            while eng.has_work():
                eng.step()
            assert b.finish_reason == "length"
        finally:
            fleet.shutdown(drain=False, timeout=30)
