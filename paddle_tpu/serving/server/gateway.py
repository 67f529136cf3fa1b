"""Async request gateway over the continuous-batching engine.

The engine is single-threaded by contract (``step()`` mutates slot
state, host length mirrors, and jitted-program caches with no locks).
This module makes it servable without breaking that contract: ONE
driver thread owns the engine and pumps ``step()``; every other thread
talks to the gateway through a thread-safe front door —

- :meth:`ServingGateway.submit` enqueues a request from any thread and
  hands back a :class:`TokenStream`, fed by the engine's ``on_token``
  callback the moment each token reaches the host: a per-token iterator
  for an in-process consumer, or, once a streaming HTTP response has
  attached its sink (:meth:`TokenStream.attach`), events that the driver
  gathers into one batch a step and hands to the writer of the SSE
  sockets (:meth:`ServingGateway._hand_over`, ``sse.py``);
- :meth:`TokenStream.cancel` flags a request from any thread; the
  driver applies it between steps via ``engine.cancel`` — the KV slot
  frees mid-decode and the ragged decode kernel skips it from the next
  step on, so cancellation costs nothing;
- admission control is a bounded waiting-room: submissions past
  ``max_queue`` raise :class:`QueueFullError` (the HTTP layer's 429)
  instead of growing an unbounded backlog;
- :meth:`ServingGateway.shutdown` drains gracefully — the front door
  closes, in-flight sequences run to completion, then the driver
  exits (or ``drain=False`` cancels everything in flight).

Deadlines ride on the engine itself (``GenerationRequest.timeout_s``,
checked at step boundaries), so a request expires whether it is queued
or mid-decode, and the gateway just observes the ``"timeout"`` finish.

The driver loop is SUPERVISED (README "Fault tolerance & chaos
testing"): an exception out of ``engine.step()`` no longer kills
serving forever. The supervisor classifies each step failure —

- **transient** (:class:`~..faults.TransientFault`, or any type in
  ``transient_types``): retry the same engine with bounded backoff; a
  streak past ``max_transient_retries`` escalates to fatal;
- **hung**: a step whose measured duration (injectable ``clock``)
  overran ``watchdog_deadline_s`` — treated as fatal, and externally
  visible either way through the
  ``serving_watchdog_last_step_age_seconds`` gauge and ``/healthz``;
- **fatal** (everything else): rebuild the engine via
  ``engine_factory`` and RECOVER every in-flight request by recompute
  — each live sequence's prompt + generated-so-far tokens are known
  host-side, so ``engine.restore()`` re-enqueues them as (chunked)
  prefills and streams continue byte-identically for greedy requests;
  the factory shares the model-level jit cache, so the rebuilt engine
  re-traces nothing (``decode_compilations()`` stays 1).

If a fault recurs while the last recovery's readmissions are still
live, the supervisor assumes a POISON request is pinned to the crash
and bisects the readmitted set: half re-enters, half parks outside the
engine; the half the fault follows keeps shrinking until a single
culprit remains, which is the ONLY request failed
(``finish_reason="error"`` — SSE clients get a final error event,
blocking clients a JSON 500) while every bystander — parked or
readmitted — runs to completion. ``max_restarts`` bounds the total
rebuild budget; past it the gateway gives up and strands with errors
(the pre-supervision behavior).

The compile-once property survives serving AND recovery: the gateway
adds no device-side work, so ``decode_compilations()`` stays at one per
``(num_slots, max_seq_len, n_steps)`` no matter the HTTP traffic mix
or how many times the engine was rebuilt — pinned by
tests/test_serving_server.py and tests/test_fault_tolerance.py.
"""
from __future__ import annotations

import atexit
import collections
import itertools
import os
import queue
import tempfile
import threading
import time
import weakref

import jax
import numpy as np

from ...profiler.cost import PROGRAM_KINDS, CostObservatory
from ...profiler.driver_clock import PHASES, DriverClock
from ...profiler.gc_watch import GENERATIONS, GcWatch
from ...profiler.metrics import (QUEUE_WAIT_BUCKETS, SPEC_ACCEPT_BUCKETS,
                                 STEP_BUCKETS, TPOT_BUCKETS, TTFT_BUCKETS,
                                 MetricsRegistry)
from ...profiler.tracing import TID_GATEWAY, SpanTracer
from ...utils.log import get_logger
from ..engine import DRAIN_REASONS, program_stat
from ..faults import TransientFault

#: engine ``stats`` counters whose /metrics series must stay monotonic
#: across crash-recovery rebuilds: a rebuilt engine starts its stats at
#: zero, so the gateway carries each dead incarnation's final count as a
#: base (the ``serving_preemptions_total`` pattern, generalized) and
#: every scrape reads base + live. Only true counters belong here —
#: gauges (headroom, last_step_*) must NOT be summed across engines.
#: MULTI-ENGINE scrapes (the fleet): the carry is PER GATEWAY — each
#: replica's gateway owns its own ``(base, engine)`` snapshot and
#: registers its series through a ``registry.labeled(replica=...)``
#: view, so N replicas share one /metrics document, every series is
#: distinguished by its ``replica`` label, and any SINGLE replica
#: rebuilding re-bases only its own series (the others never move) —
#: a fleet scrape can never observe a counter going backwards.
CARRIED_ENGINE_STATS = (
    "preemptions", "policy_preemptions",
    "prefill_chunks", "prefill_tokens_saved", "spec_proposed",
    "spec_accepted", "spec_tokens", "decode_calls", "tokens_generated",
    "mtick_syncs", "mtick_ticks", "step_prefill_tokens",
    "step_decode_tokens", "moe_pairs", "moe_experts_touched",
    "moe_max_expert_pairs", "moe_picks", "moe_compact_calls",
    "moe_layer_calls",
    "steps_dispatched_ahead", "state_rows", "state_restarts_fault",
    "state_restarts_preempt",
) + tuple("drains_" + r for r in DRAIN_REASONS)

#: same carry for the prefix cache's own stats dict (a rebuild builds a
#: fresh trie — and a fresh host tier — zeroing every counter here).
CARRIED_PREFIX_STATS = ("hits", "misses", "evictions",
                        "spilled_blocks", "tier_hits",
                        "readmitted_blocks", "tier_evictions",
                        "tier_transfers")


class QueueFullError(RuntimeError):
    """Waiting room at capacity — shed load (HTTP 429)."""


class TraceBusyError(RuntimeError):
    """A step-bounded trace capture is already in progress (HTTP 409) —
    captures serialize so two debuggers cannot clear each other's
    buffer mid-window."""


class GatewayClosedError(RuntimeError):
    """Gateway is draining or stopped — no new work (HTTP 503)."""


class WatchdogTimeout(RuntimeError):
    """An engine step overran the supervisor's watchdog deadline —
    classified "hung" and recovered like a fatal fault. (A step that
    never returns at all cannot be preempted from inside its own
    thread; it is visible externally through ``/healthz``'s
    ``last_step_age_s`` and the watchdog gauge, for an orchestrator's
    liveness probe to act on.)"""


class TokenStream:
    """Live handle for one submitted request.

    Iterating yields generated token ids as the engine produces them and
    stops when the sequence finishes; ``finish_reason`` is set by then.
    ``result()`` drains to completion and returns
    ``(ids, finish_reason)``. Both are safe from any single consumer
    thread; ``cancel()`` is safe from any thread.
    """

    def __init__(self, gateway, request, stream_id):
        self.gateway = gateway
        self.request = request
        self.id = stream_id
        self.finish_reason = None
        self.seq = None            # set by the driver at engine-submit
        self.submit_time = time.monotonic()
        self.first_token_time = None
        self.finish_time = None
        self._events = queue.SimpleQueue()  # ("token", id) | ("finish", r) | ("error", msg)
        # a streaming HTTP response's sink (``sse.py``): once attached,
        # events go to it through the driver's batch and no longer to the
        # queue. The lock orders an attachment against the driver's pushes.
        self._sink = None
        self._sink_lock = threading.Lock()
        self._collected = []
        self._cancel = False
        self._waiting = True       # still counted against max_queue
        self._drained = False      # consumer saw the finish event

    # ------------------------------------------------------- consumer side
    def __iter__(self):
        # event-driven on purpose: the driver sets finish_reason BEFORE
        # queueing the finish event, so gating on finish_reason here
        # would drop still-queued tokens of a finished stream
        while not self._drained:
            kind, payload = self._events.get()
            if kind == "token":
                self._collected.append(payload)
                yield payload
            elif kind == "finish":
                self._drained = True
            else:
                self._drained = True
                raise RuntimeError(payload)

    def result(self):
        """Block until the sequence finishes; return
        ``(np.int32 ids, finish_reason)``."""
        for _ in self:
            pass
        return np.asarray(self._collected, np.int32), self.finish_reason

    def tokens(self):
        """Tokens consumed so far (complete after ``result()`` /
        exhausting the iterator)."""
        return list(self._collected)

    @property
    def done(self):
        """Finished engine-side (tokens may still await consumption)."""
        return self.finish_reason is not None

    def cancel(self):
        """Request cancellation (idempotent, any thread). The driver
        applies it between engine steps."""
        self._cancel = True
        self.gateway._wake.set()

    def attach(self, sink):
        """Send this stream's events to ``sink`` from now on, in place of
        its iterator: ``sink.writer.write([(sink, event), ...])`` takes
        first, here, what the driver had queued, then each of the
        driver's batches takes what follows. Any thread; the one consumer
        the stream has."""
        with self._sink_lock:
            queued = []
            while not self._events.empty():
                queued.append((sink, self._events.get_nowait()))
            if queued:
                sink.writer.write(queued)
            self._sink = sink

    # --------------------------------------------------------- driver side
    def _push(self, event, batch):
        """Onto the queue an iterator reads, or with the stream's sink into
        ``batch``, which the driver hands over (``_hand_over``)."""
        with self._sink_lock:
            sink = self._sink
            if sink is None:
                self._events.put(event)
                return
        batch.append((sink, event))

    def _push_token(self, token, batch):
        self._push(("token", int(token)), batch)

    def _push_finish(self, reason, batch):
        self.finish_time = time.monotonic()
        self.finish_reason = reason
        self._push(("finish", reason), batch)

    def _push_error(self, msg, batch):
        self.finish_time = time.monotonic()
        self.finish_reason = "error"
        self._push(("error", str(msg)), batch)


class ServingGateway:
    """Thread-safe front door + engine-driver thread.

    ``max_queue`` bounds the waiting room: requests submitted but not
    yet decoding (gateway intake + engine scheduler queue). Running
    sequences never count — capacity there is ``num_slots``.
    """

    def __init__(self, engine, max_queue=64, idle_wait_s=0.02,
                 registry=None, start=True, engine_factory=None,
                 watchdog_deadline_s=None, max_transient_retries=3,
                 retry_backoff_s=0.02, max_restarts=8,
                 transient_types=(TransientFault,), clock=None,
                 fault_hook=None, tracer=None, trace=False,
                 trace_buffer=65536, cost=True, on_fatal=None,
                 stream_id_prefix="cmpl"):
        self.engine = engine
        self.max_queue = int(max_queue)
        self.idle_wait_s = float(idle_wait_s)
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._intake = collections.deque()   # TokenStreams pre engine-submit
        self._live = {}                      # seq.request_id -> TokenStream
        self._backlog = 0                    # waiting-room occupancy
        self._closed = False
        self._drain = True
        self._ids = itertools.count(1)
        # stream-id namespace: the fleet gives each replica's gateway
        # its own prefix so ids stay unique across the whole fleet
        # (completion ids are client-visible and land in the router
        # decision log)
        self._id_prefix = str(stream_id_prefix)
        # ----------------------------------------------- supervision state
        # engine_factory() -> a fresh engine with the SAME config and the
        # SAME shared jit_cache (so recovery never re-traces); None
        # disables crash recovery (a fatal fault strands, pre-PR-7 style)
        self.engine_factory = engine_factory
        self.watchdog_deadline_s = (None if not watchdog_deadline_s
                                    else float(watchdog_deadline_s))
        self.max_transient_retries = int(max_transient_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self.max_restarts = int(max_restarts)
        self.transient_types = tuple(transient_types)
        self._clock = clock if clock is not None else time.monotonic
        self._fault_hook = fault_hook        # re-installed on every rebuild
        # fleet failover hook: called (gateway, [(stream, seq|None)])
        # from the dying driver thread when supervision is exhausted,
        # BEFORE the streams are stranded with errors; returning True
        # means the callee (the fleet) took ownership — it re-admits
        # each live sequence on a sibling replica via restore() — and
        # the handed-off streams get no error event here.
        self.on_fatal = on_fatal
        self._transient_streak = 0
        self._restarts = 0
        self.last_restart_at = None          # clock() of the last rebuild
        # dead engine incarnations' summed counter stats (see
        # CARRIED_ENGINE_STATS): every /metrics series derived from
        # engine (or prefix-cache) stats reads through _stat()/
        # _pc_stat(), so a crash-recovery rebuild can never scrape as a
        # counter going backwards — pinned under the fault matrix by
        # tests/test_cost_observatory.py. The (base, pc_base, engine)
        # triple swaps in ONE attribute store: a scrape mid-rebuild
        # must never pair the new base with the old engine's stats
        # (double count, then a backwards step at the engine swap).
        # the carried counters an engine names itself: its step programs
        # by packed size (a rebuilt engine has the same sizes)
        self._carried_stats = CARRIED_ENGINE_STATS + tuple(
            program_stat(r) for r in engine.step_rows)
        self._counter_state = (dict.fromkeys(self._carried_stats, 0),
                               dict.fromkeys(CARRIED_PREFIX_STATS, 0),
                               engine)
        self._last_step_done = self._clock()
        self._recovering = False
        self._fault_at = None                # clock() of the fault being
        self.restart_latencies = []          # recovered; -> latency sample
        # poison-quarantine / bisection state (module docstring):
        self._probation = set()   # ids readmitted by the last recovery
        self._suspect_ids = None  # active bisection half (None = off)
        self._parked = []         # Sequences held out of the engine
        # live-migration intake/outtake (the fleet's request-migration
        # plane): adopt() enqueues (stream, seq) pairs arriving FROM a
        # sibling (seq None = never engine-admitted, submit fresh);
        # request_migration() enqueues (stream, handoff) pairs leaving
        # for one. Both are drained by the driver between steps — the
        # engine mutation (restore/evict) happens only on its thread.
        self._migrate_in = collections.deque()
        self._migrate_out = collections.deque()
        # the events the driver has pushed to streaming responses since
        # its last hand-over, ``[(sink, event)]`` in push order: the driver
        # thread's own (:meth:`_hand_over`)
        self._batch = []
        # ------------------------------------------------ tracing state
        # (README "Tracing & debugging") the gateway OWNS the tracer so
        # one timeline survives engine rebuilds; it is installed on
        # every engine incarnation. trace=True records from startup
        # (the --trace flag); otherwise the tracer sits disabled —
        # zero-cost — until /debug/trace?steps=N opens a capture
        # window via capture_trace(). Its engine- and gateway-lane spans
        # are mirrored into the JAX profiler's trace, so a device trace
        # taken meanwhile carries them on the device's clock.
        self.tracer = tracer if tracer is not None else \
            SpanTracer(capacity=trace_buffer, clock=self._clock,
                       annotate=jax.profiler.TraceAnnotation)
        #: public: whether tracing records continuously (``--trace``) —
        #: the HTTP layer keys its /debug/trace default on it (a
        #: parameterless GET must SNAPSHOT a persistent buffer, never
        #: clear hours of history)
        self.trace_persistent = bool(trace)
        if self.trace_persistent:
            self.tracer.enable()
        self._capture = None        # {"remaining": n, "done": Event}
        self._loop_span = None      # open from a step's end to the next
        # ---------------------------------------------- cost observatory
        # (README "Cost attribution & /debug/profile") gateway-owned
        # like the tracer, so dispatch/transfer/compile accounting is
        # monotonic across engine rebuilds; ON by default (host-side
        # dict updates, a handful per step) — ``cost=False`` reduces
        # every engine cost site to the one _co() attribute check.
        self.cost = CostObservatory(clock=self._clock) if cost else None
        self._pcapture = None       # /debug/profile capture window
        # ------------------------------------------------ driver clock
        # (README "Tracing & debugging") where the driver thread's time
        # goes, by phase, on the wall and on the thread's CPU clock:
        # always on, gateway-owned like the two above, marked at the
        # boundaries where the spans are (``_mark`` here and on the
        # engine); a tracer injected with a clock of its own reads that
        self.driver_clock = DriverClock(
            wall=self._clock,
            stamps_spans=self.tracer.clock is self._clock,
            on_mark=self._hand_over)
        # the collector's pauses, by generation: counted while the driver
        # thread runs (:meth:`_run` installs and removes the callback),
        # and a ``gc`` span each while the tracer records on a real clock
        self.gc_watch = GcWatch(wall=self._clock, tracer=self.tracer)
        engine.tracer = self.tracer
        engine.cost = self.cost
        engine.driver_clock = self.driver_clock
        engine.on_token = self._on_token
        engine.on_finish = self._on_finish
        engine.on_policy_preempt = self._on_policy_preempt
        engine.on_step = self._on_step
        if fault_hook is not None:
            engine.fault_hook = fault_hook
        self._init_metrics(registry)
        self._thread = threading.Thread(target=self._run,
                                        name="engine-driver", daemon=True)
        # a daemon driver killed mid-XLA-dispatch at interpreter teardown
        # aborts the process (observed: LLVM "Invalid size request") —
        # stop it via atexit instead. weakref so the hook never keeps a
        # dropped gateway alive.
        ref = weakref.ref(self)
        self._atexit_hook = lambda: (lambda gw: gw and gw.shutdown(
            drain=False, timeout=10))(ref())
        atexit.register(self._atexit_hook)
        if start:
            self.start()

    def start(self):
        """Start the engine-driver thread (for gateways built with
        ``start=False`` — tests and benches submit their whole workload
        first so a fault plan's step indices are deterministic relative
        to the traffic). Idempotent once running; returns self."""
        if not self._thread.is_alive():
            self._thread.start()
        return self

    # ------------------------------------------------------------- helpers
    def _tr(self):
        """The tracer when recording, else None — the gateway's guard
        for its own instrumentation sites (the engine's ``_tr()``
        discipline; the guard-discipline static test pins that every
        recording site in ``serving/`` routes through one of these)."""
        t = self.tracer
        return t if t.enabled else None

    @property
    def _stat_base(self) -> dict:
        """Dead incarnations' summed engine-stat counters."""
        return self._counter_state[0]

    def _stat(self, key) -> int:
        """A monotonic engine-stat counter: dead incarnations' carried
        base + the live engine's count (CARRIED_ENGINE_STATS). Reads
        base and engine from ONE snapshot so a mid-rebuild scrape
        cannot mix epochs."""
        base, _, eng = self._counter_state
        return base[key] + eng.stats[key]

    def _pc_stat(self, key) -> int:
        """Same carry for prefix-cache stats (zero with no trie)."""
        _, pc_base, eng = self._counter_state
        pc = eng.prefix_cache
        return pc_base[key] + (pc.stats[key] if pc is not None else 0)

    def _class_labels(self, seq) -> dict:
        """Label kwargs for one sequence's latency observations: the
        ``class`` label with a MULTI-CLASS table active, {} otherwise —
        a policy-off gateway's histogram series keep their empty label
        sets, byte-identical to before the policy subsystem existed."""
        if self._m_slo_miss is None:
            return {}
        pclass = getattr(seq, "pclass", None)
        return {"class": pclass.name} if pclass is not None else {}

    # ------------------------------------------------------------- metrics
    def _init_metrics(self, registry):
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        r = self.registry
        self._m_requests = r.counter(
            "serving_requests_total", "Requests accepted by the gateway.")
        self._m_rejected = r.counter(
            "serving_rejected_total",
            "Requests shed by admission control (queue full).")
        self._m_finished = r.counter(
            "serving_finished_total",
            "Finished sequences by finish_reason.")
        self._m_tokens = r.counter(
            "serving_generated_tokens_total", "Generated tokens.")
        self._m_ttft = r.histogram(
            "serving_ttft_seconds", "Submit-to-first-token latency.",
            buckets=TTFT_BUCKETS)
        self._m_latency = r.histogram(
            "serving_request_latency_seconds",
            "Submit-to-finish latency per request.")
        # SLO substrate (ROADMAP multi-tenant item b): per-request
        # latency decomposition the TTFT/TPOT-target scheduler will
        # consume. Both are gateway-owned and read the Sequence's
        # engine-clock stamps at retirement, so they survive engine
        # rebuilds and keep accumulating across restarts.
        self._m_tpot = r.histogram(
            "serving_tpot_seconds",
            "Per-request time-per-output-token: (finish - first token)"
            " / (tokens - 1), the steady-state decode cadence one "
            "request observed (engine clock; requests with a single "
            "token have no inter-token gap and are not observed).",
            buckets=TPOT_BUCKETS)
        self._m_queue_wait = r.histogram(
            "serving_queue_wait_seconds",
            "Per-request submit-to-slot-claim wait (engine clock) — "
            "the admission-control half of TTFT. Never-admitted "
            "requests (queued timeout/cancel) are not observed.",
            buckets=QUEUE_WAIT_BUCKETS)
        r.gauge("serving_queue_depth",
                "Requests waiting for a slot (intake + scheduler queue)."
                ).set_fn(lambda: self._backlog)
        r.gauge("serving_active_slots",
                "KV slots currently decoding.").set_fn(
            lambda: self.engine.num_active)
        r.gauge("serving_num_slots", "KV slot capacity.").set(
            self.engine.num_slots)
        step_tokens = r.counter(
            "serving_step_tokens_total",
            "Tokens the step programs processed, by kind: prefill "
            "(chunk tokens packed into the step) and decode (decode "
            "rows times their fused ticks). rate() of the decode "
            "series is generated tokens/s. Monotonic across engine "
            "rebuilds.")
        step_tokens.set_fn(lambda: self._stat("step_prefill_tokens"),
                           kind="prefill")
        step_tokens.set_fn(lambda: self._stat("step_decode_tokens"),
                           kind="decode")
        # the pipeline of the unified step (README "Serving"): over
        # serving_step_duration_seconds_count the first is the share of
        # steps whose program went to the chip behind another one
        r.counter(
            "serving_steps_dispatched_ahead_total",
            "Step programs dispatched while the previous one was still in "
            "flight (its tokens not yet read by the host). Monotonic "
            "across engine rebuilds."
        ).set_fn(lambda: self._stat("steps_dispatched_ahead"))
        programs = r.counter(
            "serving_step_programs_total",
            "Step programs fenced, by the rows of their packed buffer: a "
            "unified step with no prefill chunk runs at num_slots rows "
            "(rounded up to 8), one with a chunk at num_slots + "
            "prefill_chunk. On the default step the sizes sum to "
            "serving_step_duration_seconds_count. "
            "Monotonic across engine rebuilds.")
        for rows in self.engine.step_rows:
            programs.set_fn(lambda k=program_stat(rows): self._stat(k),
                            rows=str(rows))
        drains = r.counter(
            "serving_pipeline_drains_total",
            "Times the program in flight was fenced and accepted with "
            "none dispatched behind it, by reason: idle (nothing to "
            "dispatch), cancel, evict, preempt, pool, deadline, snapshot "
            "(recovery), fault (a fence raised; dropped, not accepted). "
            "Monotonic across engine rebuilds.")
        for reason in DRAIN_REASONS:
            drains.set_fn(
                lambda reason=reason: self._stat("drains_" + reason),
                reason=reason)
        if self.engine.routed_ffn:
            # a routed-FFN model only: a dense model's /metrics document
            # stays as it was. Each is summed over the layer calls of
            # every step and whole-prompt prefill; ratios of their rates
            # are per-layer-call means.
            for stat, text in (
                    ("pairs", "Live (token, expert) pairs the routed FFN "
                     "multiplied: the picks that landed on an expert "
                     "this engine holds."),
                    ("picks", "Experts the live tokens picked over the "
                     "router's whole width (pairs again when every "
                     "expert is held here)."),
                    ("experts_touched", "Experts some live pair touched "
                     "(whose weights a layer call read)."),
                    ("max_expert_pairs", "Pairs on the fullest expert of "
                     "each layer call."),
                    ("compact_calls", "Routed-FFN layer calls that ran one "
                     "pass on a pair buffer sized for the pairs this "
                     "engine's experts take, not for every pick (over "
                     "layer_calls: their share)."),
                    ("layer_calls", "Routed-FFN layer calls (layers x "
                     "program calls).")):
                r.counter(f"serving_moe_{stat}_total",
                          text + " Monotonic across engine rebuilds."
                          ).set_fn(lambda k="moe_" + stat: self._stat(k))
        if self.engine.cache.store:
            # a model with recurrent or window layers only (a hybrid
            # model's linear layers, a window layer's ring): their cache is
            # a store by slot beside the KV pool, each kind's bytes apart
            if self.engine.cache.state is not None:
                r.gauge("serving_state_bytes_per_slot",
                        "HBM bytes one slot's recurrent states and "
                        "convolution tails hold over all their layers, "
                        "whatever the sequence's length."
                        ).set_fn(
                    lambda: self.engine.cache.state_bytes_per_slot)
            if self.engine.cache.window is not None:
                r.gauge("serving_window_bytes_per_slot",
                        "HBM bytes one slot's rings of window keys and "
                        "values hold over all the window layers, whatever "
                        "the sequence's length."
                        ).set_fn(
                    lambda: self.engine.cache.window_bytes_per_slot)
            r.counter("serving_state_rows_total",
                      "Spans whose slot's recurrent state a program read "
                      "and wrote (decode rows, prefill chunks, whole "
                      "prompts), summed over programs; one layer's count. "
                      "Monotonic across engine rebuilds."
                      ).set_fn(lambda: self._stat("state_rows"))
            restarts = r.counter(
                "serving_state_restarts_total",
                "Live sequences sent back to position 0 because their "
                "recurrent state could not be replayed, by reason: fault "
                "(a fence raised after programs had applied their tokens), "
                "preempt (displaced; the state is recomputed, never "
                "resumed). Monotonic across engine rebuilds.")
            for reason in ("fault", "preempt"):
                restarts.set_fn(
                    lambda reason=reason: self._stat(
                        "state_restarts_" + reason), reason=reason)
        r.gauge("serving_decode_compilations",
                "Decode-program traces (compile-once contract: stays at "
                "one per (packed size reached, n_steps), at most two "
                "sizes).").set_fn(
            self.engine.decode_compilations)
        r.counter("serving_prefill_chunks_total",
                  "Chunked-prefill device chunks run (one per sequence "
                  "per step while a long cold prompt is interleaved "
                  "with decode; 0 with chunking off). Monotonic "
                  "across engine rebuilds.").set_fn(
            lambda: self._stat("prefill_chunks"))
        # per-step telemetry: the SAME duration/token measurements the
        # engine's headroom EWMAs (adaptive chunk budget) read — observed
        # once per step program FENCED (``engine.on_step``), inside a
        # step() or at a drain outside one, so _count counts programs
        self._m_step_dur = r.histogram(
            "serving_step_duration_seconds",
            "What one step program cost, fence to fence (admission + "
            "prefill grant + decode + retire; the driver's loop between "
            "two steps too).", buckets=STEP_BUCKETS)
        driver = r.counter(
            "serving_driver_seconds_total",
            "Seconds of the engine-driver thread by phase (loop: the "
            "gateway between two steps; idle-wait: no work; sweep, admit, "
            "plan, dispatch, device-wait, host-accept, retire: the step's "
            "spans of those names; other: the rest of a step) and clock "
            "(wall; "
            "cpu: the thread's own CPU time). The wall phases sum to the "
            "thread's elapsed time. device-wait over all but idle-wait "
            "(wall) is the share of its time the host waits for the "
            "chip: near 0 the host is the pace. Monotonic across engine "
            "rebuilds.")
        long_n = r.counter(
            "serving_driver_long_visits_total",
            "Visits of the engine-driver thread to one phase, mark to "
            "mark, that lasted longer than driver_clock.LONG_VISIT_S "
            "(8 ms): a host stall that long empties the one-deep "
            "pipeline. The waiting phases are counted like the others.")
        long_s = r.counter(
            "serving_driver_long_visit_seconds_total",
            "Wall seconds of those visits, by phase.")
        for phase in PHASES:
            for clock in ("wall", "cpu"):
                driver.set_fn(
                    lambda p=phase, c=clock: self.driver_clock.seconds(p, c),
                    phase=phase, clock=clock)
            long_n.set_fn(lambda p=phase: self.driver_clock.long_visits[p],
                          phase=phase)
            long_s.set_fn(lambda p=phase: self.driver_clock.long_visit_s[p],
                          phase=phase)
        gc_s = r.counter(
            "serving_gc_pause_seconds_total",
            "Seconds the collector held every thread of the process, by "
            "generation (gateway clock, start to stop of a collection, "
            "whichever thread it ran on), while the driver thread ran.")
        gc_n = r.counter(
            "serving_gc_collections_total",
            "Collections, by generation, while the driver thread ran.")
        for gen in GENERATIONS:
            gc_s.set_fn(lambda g=gen: self.gc_watch.pause_s[g],
                        generation=str(gen))
            gc_n.set_fn(lambda g=gen: self.gc_watch.collections[g],
                        generation=str(gen))
        r.gauge("serving_step_tokens",
                "Tokens the last engine step processed on device "
                "(decode rows x fused ticks + prefill chunk tokens)."
                ).set_fn(lambda: self.engine.stats["last_step_tokens"])
        r.gauge("serving_prefill_headroom_tokens",
                "Current headroom-adaptive chunk-token grant per step "
                "(prefill_chunk is the cap; fixed at it until the "
                "EWMAs have signal or with adaptivity off).").set_fn(
            lambda: self.engine.stats["headroom"])
        # multi-tick decode surface (README "Multi-tick decode"):
        # mean on-device decode ticks per host sync — 1.0 means the
        # host is back in the loop every token, decode_ticks means the
        # fast path is fully engaged. Counters ride the _stat() carry,
        # so a rebuild never dents the ratio.
        r.gauge("serving_decode_ticks_per_sync",
                "Mean fused on-device decode ticks per host sync on "
                "the multi-tick engine (decode_ticks=1 engines and "
                "engines that never decoded scrape 0).").set_fn(
            lambda: (self._stat("mtick_ticks")
                     / max(self._stat("mtick_syncs"), 1)))
        # speculative-decode surface (README "Speculative decoding"):
        # registered only on a speculative engine, read THROUGH
        # self.engine so a recovery rebuild re-binds them (same idiom
        # as the paged/prefix gauges below). Counters read through the
        # _stat() carry, so a rebuild never scrapes as a reset.
        self._m_spec_len = None
        if getattr(self.engine, "spec_decode", False):
            r.counter("serving_spec_proposed_total",
                      "Draft tokens submitted to verification. "
                      "Monotonic across engine rebuilds."
                      ).set_fn(lambda: self._stat("spec_proposed"))
            r.counter("serving_spec_accepted_total",
                      "Draft tokens accepted (emitted without their own "
                      "decode launch) — the speculation win. Monotonic "
                      "across engine rebuilds.").set_fn(
                lambda: self._stat("spec_accepted"))
            self._m_spec_len = r.histogram(
                "serving_spec_accept_length",
                "Tokens emitted per verify span (1 = nothing accepted, "
                "spec_k + 1 = full draft accepted).",
                buckets=SPEC_ACCEPT_BUCKETS)
            # numerator is decode_calls, NOT spec_steps: a spec engine
            # increments decode_calls only for launches that carried
            # verify rows, while spec_steps also counts chunk-only
            # launches whose tokens never enter spec_tokens — those
            # would inflate a ratio defined over decode work
            r.gauge("serving_spec_launches_per_accepted_token",
                    "Decode launches per emitted token under "
                    "speculation (1.0 = no speedup; ~1 / mean "
                    "acceptance length).").set_fn(
                lambda: (self._stat("decode_calls")
                         / max(self._stat("spec_tokens"), 1)))
        # fault-tolerance surface (README "Fault tolerance & chaos
        # testing"). Gateway-owned counters, NOT engine-stat-backed:
        # engine stats die with a rebuilt engine, and a restart must
        # never scrape as a counter reset.
        self._m_faults = r.counter(
            "serving_faults_total",
            "Engine step faults observed by the supervisor, by class "
            "(kind = transient|fatal|hung).")
        self._m_restarts = r.counter(
            "serving_engine_restarts_total",
            "Engine rebuilds after a fatal/hung step fault (recovery-"
            "by-recompute; the jit cache is shared, so a restart "
            "re-traces nothing).")
        self._m_recovered = r.counter(
            "serving_recovered_requests_total",
            "Live requests re-enqueued for recompute after an engine "
            "rebuild (each readmission counts, including bisection "
            "re-entries).")
        # zero-seed the label-free incremented counters so every
        # gateway's series exists from the first scrape — a fleet
        # replica that never restarted must scrape as an explicit 0,
        # not an absent series (dashboards diff replicas)
        for m in (self._m_requests, self._m_rejected, self._m_tokens,
                  self._m_restarts, self._m_recovered):
            m.inc(0)
        r.counter("serving_preemptions_total",
                  "Sequences preempted by recompute under KV pool "
                  "pressure (PoolExhausted: chain donated to the trie, "
                  "request re-queued). Monotonic across engine rebuilds."
                  ).set_fn(lambda: self._stat("preemptions"))
        # multi-tenant SLO surface (README "Multi-tenant SLO serving"):
        # registered only when the engine's class table is ACTIVE, so a
        # policy-off gateway's /metrics document — and the empty label
        # sets on the latency histograms — stays byte-identical to
        # before the subsystem existed. Both counters are gateway-owned
        # inc-based (the _m_faults idiom, NOT engine-stat-backed), so
        # they are monotonic across engine rebuilds by construction;
        # zero-seeded per known class so dashboards can diff tenants
        # from the first scrape.
        self._m_slo_miss = None
        self._m_policy_preempt = None
        if self.engine.classes.active:
            self._m_slo_miss = r.counter(
                "serving_slo_misses_total",
                "Finished first-tokens/requests that exceeded their "
                "priority class's SLO target, by class and slo "
                "(ttft|tpot). Classes without a target never miss.")
            self._m_policy_preempt = r.counter(
                "serving_policy_preemptions_total",
                "Sequences displaced by SLO-driven policy preemption "
                "(an urgent higher-class request claimed the slot), "
                "by the victim's class. Streams continue "
                "byte-identically after restore.")
            for c in self.engine.classes:
                self._m_policy_preempt.inc(0, victim_class=c.name)
                for slo in ("ttft", "tpot"):
                    self._m_slo_miss.inc(0, **{"class": c.name,
                                               "slo": slo})
        r.gauge("serving_watchdog_last_step_age_seconds",
                "Seconds since the last completed engine step (the "
                "supervisor's hung-step signal; an orchestrator's "
                "external liveness probe for a step that never "
                "returns).").set_fn(self.last_step_age)
        # pool/prefix gauges read THROUGH self.engine at scrape time:
        # a recovery rebuild swaps the engine (and its cache/pool/trie)
        # underneath the registry, and the gauges must follow it rather
        # than keep reporting a dead engine's bookkeeping
        # paged-attention surface: physical sharing + table pressure
        # (scrape-time reads of host bookkeeping; driver is the only
        # writer, a scrape reads ints under the GIL)
        r.gauge("kv_blocks_shared",
                "Pool blocks physically shared by concurrent "
                "readers (refcount >= 2) — the zero-copy win."
                ).set_fn(lambda: self.engine.cache.pool.num_shared)
        r.gauge("kv_block_table_fill",
                "Fraction of the [num_slots, max_blocks] block "
                "table grid populated by live sequences."
                ).set_fn(lambda: self.engine.cache.table_fill())
        # quantized-serving surface (README "Quantized serving"):
        # pool HBM in BYTES, dtype-aware via
        # PagedKVCache.occupancy_bytes() — an int8 pool reports
        # int8 data bytes under kind="kv" plus its fp32 scale
        # planes under kind="scales" (0 on the default pool), and
        # the per-cached-token marginal HBM cost. Allocated (live +
        # trie) blocks x per-block bytes.
        kvb = r.gauge(
            "kv_pool_bytes",
            "Allocated KV pool HBM bytes by storage kind (kv = "
            "block data at the pool dtype, scales = the int8 "
            "pool's fp32 scale planes; 0 when unquantized).")
        # each kind scans the block tables once (used_blocks);
        # per-token is pure constants — a scrape pays two cheap
        # scans total, never three occupancy_bytes() walks
        kvb.set_fn(
            lambda: (self.engine.cache.used_blocks()
                     * self.engine.cache.pool.block_nbytes),
            kind="kv")
        kvb.set_fn(
            lambda: (self.engine.cache.used_blocks()
                     * self.engine.cache.pool.scale_block_nbytes),
            kind="scales")
        r.gauge("serving_kv_bytes_per_token",
                "Marginal HBM bytes one cached token costs (block "
                "bytes incl. scale planes / block_size) — the "
                "denominator of the quantized-density win."
                ).set_fn(
            lambda: self.engine.cache.bytes_per_token())
        if self.engine.cache.index_bytes_per_token:
            r.gauge("serving_index_bytes_per_token",
                    "HBM bytes one cached token's index keys hold over "
                    "the layers that have an indexer (sparse attention's "
                    "second per-token cache, beside "
                    "serving_kv_bytes_per_token's latent rows)."
                    ).set_fn(
                lambda: self.engine.cache.index_bytes_per_token)
        if getattr(self.engine, "prefix_cache", None) is not None:
            # scrape-time counters backed by the cache's own stats plus
            # the gateway's carried base (the driver thread is the only
            # writer; a scrape reads one int — no sync needed beyond
            # the GIL). A rebuild starts a fresh trie, but the base
            # keeps the series monotonic across it.
            r.counter("serving_prefix_cache_hits_total",
                      "Admissions that matched a cached prefix chain. "
                      "Monotonic across engine rebuilds.").set_fn(
                lambda: self._pc_stat("hits"))
            r.counter("serving_prefix_cache_misses_total",
                      "Admissions with no cached prefix. Monotonic "
                      "across engine rebuilds.").set_fn(
                lambda: self._pc_stat("misses"))
            r.counter("serving_prefix_cache_evictions_total",
                      "Cached blocks evicted under pool pressure. "
                      "Monotonic across engine rebuilds.").set_fn(
                lambda: self._pc_stat("evictions"))
            r.counter("serving_prefill_tokens_saved_total",
                      "Prompt tokens served from cached KV blocks "
                      "instead of device prefill. Monotonic across "
                      "engine rebuilds.").set_fn(
                lambda: self._stat("prefill_tokens_saved"))
            r.gauge("kv_prefix_blocks",
                    "Prefix-cache pool blocks in use (published + "
                    "pinned).").set_fn(
                lambda: self.engine.prefix_cache.pool.num_used)
            r.gauge("kv_prefix_blocks_capacity",
                    "Prefix-cache pool size in blocks.").set_fn(
                lambda: self.engine.prefix_cache.pool.num_blocks)
            r.gauge("serving_prefix_cached_blocks",
                    "Trie-resident cached blocks (live nodes) — the "
                    "HBM half of the tiered cache.").set_fn(
                lambda: self.engine.prefix_cache.num_cached_blocks)
            # host-RAM spill tier (README "Tiered KV prefix cache"):
            # counters registered unconditionally so a tierless engine
            # scrapes explicit zeros; occupancy gauges read 0 with the
            # tier off.
            r.counter("serving_prefix_spilled_blocks_total",
                      "Evicted trie blocks spilled device->host into "
                      "the tier instead of deleted. Monotonic across "
                      "engine rebuilds.").set_fn(
                lambda: self._pc_stat("spilled_blocks"))
            r.counter("serving_prefix_tier_hits_total",
                      "Recording lookups that readmitted at least one "
                      "spilled block from the host tier. Monotonic "
                      "across engine rebuilds.").set_fn(
                lambda: self._pc_stat("tier_hits"))
            r.counter("serving_prefix_readmitted_blocks_total",
                      "Spilled blocks streamed back host->device and "
                      "re-linked as live trie nodes. Monotonic across "
                      "engine rebuilds.").set_fn(
                lambda: self._pc_stat("readmitted_blocks"))
            r.counter("serving_prefix_tier_evictions_total",
                      "Tier entries dropped by the host-side LRU under "
                      "the host_tier_bytes budget. Monotonic across "
                      "engine rebuilds.").set_fn(
                lambda: self._pc_stat("tier_evictions"))
            r.counter("serving_prefix_tier_transfers_total",
                      "Spilled chains admitted host-to-host from a "
                      "sibling replica's tier (the fleet cache plane). "
                      "Monotonic across engine rebuilds.").set_fn(
                lambda: self._pc_stat("tier_transfers"))
            r.gauge("serving_prefix_tier_blocks",
                    "Blocks resident in the host-RAM spill tier."
                    ).set_fn(
                lambda: (self.engine.prefix_cache.tier.num_blocks
                         if self.engine.prefix_cache.tier is not None
                         else 0))
            r.gauge("serving_prefix_tier_bytes",
                    "Host bytes the spill tier currently holds."
                    ).set_fn(
                lambda: (self.engine.prefix_cache.tier.bytes_used
                         if self.engine.prefix_cache.tier is not None
                         else 0))
            r.gauge("serving_prefix_tier_bytes_capacity",
                    "The host_tier_bytes budget (0 = tier off)."
                    ).set_fn(
                lambda: (self.engine.prefix_cache.host_tier_bytes))
        # device-boundary cost surface (README "Cost attribution &
        # /debug/profile"): observatory-owned, so every series is
        # monotonic across engine rebuilds by construction. One series
        # per program kind is registered up front — unused kinds scrape
        # as 0 rather than appearing mid-flight.
        if self.cost is not None:
            co = self.cost
            disp = r.counter(
                "serving_dispatches_total",
                "Device program launches by program kind — the exact "
                "host->device dispatch count the mega-kernel work is "
                "measured against. Monotonic across engine rebuilds.")
            for kind in PROGRAM_KINDS:
                disp.set_fn((lambda k: lambda: co.kind_calls(k))(kind),
                            program=kind)
            xfer = r.counter(
                "serving_transfer_bytes_total",
                "Host<->device boundary bytes from abstract shapes "
                "(h2d: host-resident argument leaves uploaded at "
                "dispatch; d2h: result leaves the engine fetches to "
                "host). No device sync; monotonic across rebuilds.")
            xfer.set_fn(lambda: co.totals["h2d_bytes"], direction="h2d")
            xfer.set_fn(lambda: co.totals["d2h_bytes"], direction="d2h")
            # tensor-parallel collective surface (README "Tensor-
            # parallel serving"): cross-chip all-reduce wire bytes by
            # wire dtype — a SEPARATE ledger from h2d/d2h (all-reduce
            # traffic never crosses the host boundary, and logical
            # per-shard arg leaves are never double-counted into it).
            # Registered up front for both dtypes so tp=1 engines
            # scrape explicit zeros, not absent series.
            coll = r.counter(
                "serving_collective_bytes_total",
                "Cross-chip tensor-parallel all-reduce wire bytes per "
                "device by collective dtype (exact, shape-derived — "
                "the EQuARX int8 wire cut is this counter's fp/int8 "
                "ratio). 0 on tp=1 engines. Monotonic across engine "
                "rebuilds.")
            for cdt in ("fp", "int8"):
                coll.set_fn((lambda d: lambda: co.collective_bytes(d))(
                    cdt), dtype=cdt)
            # KV-tier cache-plane traffic by direction — the same
            # separate-ledger rule as collectives: spill/readmit bytes
            # never land in the per-program h2d/d2h records.
            # Registered up front for all three directions so tierless
            # engines scrape explicit zeros.
            tier = r.counter(
                "serving_tier_bytes_total",
                "KV prefix-tier bytes moved by direction (d2h: spill, "
                "h2d: readmission, peer: fleet host-to-host transfer "
                "in). A separate ledger from serving_transfer_bytes_"
                "total — cache-plane traffic never pollutes per-program "
                "transfer baselines. Monotonic across engine rebuilds.")
            for tdir in ("d2h", "h2d", "peer"):
                tier.set_fn((lambda d: lambda: co.tier_bytes(d))(tdir),
                            direction=tdir)
            r.counter("serving_program_compiles_total",
                      "Program compile (trace) events observed at the "
                      "jit-cache chokepoint — stays flat once warm "
                      "(the compile-once contract, including across "
                      "rebuilds).").set_fn(
                lambda: co.totals["compiles"])
            r.gauge("serving_dispatches_per_decoded_token",
                    "Device program launches per generated token "
                    "(all program kinds / all tokens since start) — "
                    "the ROADMAP mega-kernel item's headline."
                    ).set_fn(
                lambda: (co.totals["dispatches"]
                         / max(self._stat("tokens_generated"), 1)))

    # ---------------------------------------------------------- front door
    def submit(self, request) -> TokenStream:
        """Enqueue from any thread. Raises ValueError/TypeError on a bad
        request, QueueFullError past ``max_queue``, GatewayClosedError
        after shutdown began."""
        # validate on the caller's thread: a bad request must 400 here,
        # not poison the driver loop later
        self.engine.validate(request)
        with self._lock:
            if self._closed:
                raise GatewayClosedError("gateway is draining")
            if self._backlog >= self.max_queue:
                self._m_rejected.inc()
                raise QueueFullError(
                    f"waiting room full ({self.max_queue} requests)")
            self._backlog += 1
            stream = TokenStream(self, request,
                                 f"{self._id_prefix}-{next(self._ids)}")
            self._intake.append(stream)
        self._m_requests.inc()
        self._wake.set()
        return stream

    def adopt(self, stream, seq=None):
        """Take over a live request from a sibling gateway (fleet
        failover / live migration). Thread-safe: enqueues the pair; the
        driver re-admits between steps — ``seq`` (the sibling's evicted
        / crash-snapshotted Sequence, PRNG walk included) re-enters via
        ``engine.restore`` so its stream continues byte-identically,
        while ``seq=None`` (a request the sibling never engine-
        admitted) submits fresh. The stream is re-pointed at THIS
        gateway, so cancellation and token delivery follow it over."""
        with self._lock:
            if self._closed:
                raise GatewayClosedError("gateway is draining")
            stream.gateway = self
            if stream._waiting:
                # the waiting-room seat moves with the stream (the
                # source decremented its own count at handoff)
                self._backlog += 1
            self._migrate_in.append((stream, seq))
        self._wake.set()

    def request_migration(self, stream, handoff):
        """Ask the driver to evict ``stream``'s live sequence from this
        engine between steps and call ``handoff(stream, seq)`` — on the
        driver thread — once it is displaced (chain donated, PRNG
        snapshotted; ``seq`` is None when the request never reached the
        engine). The fleet's handoff adopts the pair on a sibling.
        Thread-safe; a no-op for streams that finish first."""
        with self._lock:
            self._migrate_out.append((stream, handoff))
        self._wake.set()

    @property
    def queue_depth(self):
        return self._backlog

    @property
    def closed(self):
        return self._closed

    # ------------------------------------------------------- engine events
    def _leave_waiting_room(self, stream):
        if stream._waiting:
            stream._waiting = False
            with self._lock:
                self._backlog -= 1

    def _on_token(self, seq, token):
        stream = self._live.get(seq.request_id)
        self._m_tokens.inc()
        if stream is None:
            return
        if stream.first_token_time is None:
            stream.first_token_time = time.monotonic()
            self._m_ttft.observe(stream.first_token_time
                                 - stream.submit_time,
                                 **self._class_labels(seq))
            self._leave_waiting_room(stream)
            # TTFT SLO verdict from the ENGINE-clock stamp (not the
            # wall-clock wire latency above): deterministic under an
            # injected clock, so chaos replays count identical misses
            if self._m_slo_miss is not None:
                pclass = getattr(seq, "pclass", None)
                ttft = seq.ttft_s
                if (pclass is not None and pclass.ttft_slo_s is not None
                        and ttft is not None
                        and ttft > pclass.ttft_slo_s):
                    self._m_slo_miss.inc(**{"class": pclass.name,
                                            "slo": "ttft"})
        stream._push_token(token, self._batch)

    def _finish_teardown(self, seq):
        """Bookkeeping shared by every terminal path — engine finishes
        (:meth:`_on_finish`) and the quarantine's poison conviction
        (:meth:`_fail_poisoned`) — so metrics and quarantine state
        cannot drift between them. Returns the stream (if any) still
        owed its terminal event."""
        stream = self._live.pop(seq.request_id, None)
        self._m_finished.inc(reason=seq.finish_reason)
        # SLO decomposition from the Sequence's engine-clock stamps
        # (None-guarded: a queued timeout was never admitted, a
        # one-token request has no TPOT)
        qw = seq.queue_wait_s
        if qw is not None:
            self._m_queue_wait.observe(qw, **self._class_labels(seq))
        tp = seq.tpot_s
        if tp is not None:
            self._m_tpot.observe(tp, **self._class_labels(seq))
            if self._m_slo_miss is not None:
                pclass = getattr(seq, "pclass", None)
                if (pclass is not None and pclass.tpot_slo_s is not None
                        and tp > pclass.tpot_slo_s):
                    self._m_slo_miss.inc(**{"class": pclass.name,
                                            "slo": "tpot"})
        # quarantine bookkeeping: any terminal outcome clears suspicion
        self._probation.discard(seq.request_id)
        if self._suspect_ids is not None:
            self._suspect_ids.discard(seq.request_id)
        if stream is None:
            return None
        self._leave_waiting_room(stream)  # finished while still queued
        self._m_latency.observe(time.monotonic() - stream.submit_time)
        return stream

    def _on_finish(self, seq):
        stream = self._finish_teardown(seq)
        if stream is not None:
            stream._push_finish(seq.finish_reason, self._batch)

    def _on_policy_preempt(self, seq):
        """Engine hook: an SLO-urgent request displaced ``seq``. Counts
        by victim class on the gateway-owned counter (monotonic across
        rebuilds — the engine's own policy_preemptions stat rides the
        CARRIED_ENGINE_STATS carry in parallel)."""
        if self._m_policy_preempt is not None:
            pclass = getattr(seq, "pclass", None)
            self._m_policy_preempt.inc(
                victim_class=pclass.name if pclass is not None
                else "unknown")

    def _on_step(self, duration_s):
        """Engine hook: one step program was fenced and booked."""
        self._m_step_dur.observe(duration_s)

    def _hand_over(self):
        """Give the writer of the SSE sockets what the driver has pushed to
        streaming responses since the last call, in one batch. The driver
        clock calls this at every mark, before it reads its clocks: a
        step's tokens and finishes leave at the end of the ``host-accept``
        that saw them and are charged to it, a refusal, a cancellation or
        a conviction between two steps leaves at the loop's next mark, and
        no event waits through a ``device-wait`` or an ``idle-wait``."""
        batch = self._batch
        if batch:
            self._batch = []
            # one HTTP server's sinks share its one writer
            batch[0][0].writer.write(batch)

    # ------------------------------------------------------- driver thread
    def _admit_intake(self):
        while True:
            with self._lock:
                if not self._intake:
                    return
                stream = self._intake.popleft()
            if stream._cancel:
                self._leave_waiting_room(stream)
                self._m_finished.inc(reason="cancelled")
                stream._push_finish("cancelled", self._batch)
                continue
            try:
                seq = self.engine.submit(stream.request)
            except Exception as e:  # validated at submit(); belt+braces
                self._leave_waiting_room(stream)
                stream._push_error(e, self._batch)
                continue
            stream.seq = seq
            self._live[seq.request_id] = stream

    def _admit_migrations(self):
        """Driver-side intake of requests adopted from a sibling
        gateway (fleet failover / live migration): a carried Sequence
        re-enters via ``engine.restore`` — recompute from host token
        state + the PRNG snapshot, so the stream continues
        byte-identically — and a bare request (never engine-admitted on
        the source) submits fresh. Cancellation that raced the
        migration is honored here, exactly like the intake path."""
        while True:
            with self._lock:
                if not self._migrate_in:
                    return
                stream, seq = self._migrate_in.popleft()
            if stream._cancel:
                if seq is not None and not seq.done:
                    seq.status = "finished"
                    seq.finish_reason = "cancelled"
                self._leave_waiting_room(stream)
                self._m_finished.inc(reason="cancelled")
                stream._push_finish("cancelled", self._batch)
                continue
            if seq is None:
                try:
                    seq = self.engine.submit(stream.request)
                except Exception as e:
                    self._leave_waiting_room(stream)
                    stream._push_error(e, self._batch)
                    continue
            elif seq.done:
                # finished in flight between gateways (shouldn't
                # happen — eviction only hands off live sequences —
                # but a terminal event beats a stranded consumer)
                self._leave_waiting_room(stream)
                self._m_finished.inc(reason=seq.finish_reason)
                stream._push_finish(seq.finish_reason, self._batch)
                continue
            elif (seq.prompt_len + int(seq.request.max_new_tokens)
                    > self.engine.max_seq_len):
                # belt + braces under the fleet's can_hold selection:
                # an adoption this engine cannot hold to completion
                # must terminate cleanly, never crash the driver
                # mid-recompute (which would count as a fatal fault
                # and cascade a fresh failover of the same sequence)
                self._leave_waiting_room(stream)
                stream._push_error(
                    f"migrated sequence needs "
                    f"{seq.prompt_len + int(seq.request.max_new_tokens)}"
                    f" KV rows; this engine holds "
                    f"{self.engine.max_seq_len}", self._batch)
                continue
            elif self.engine.restore(seq):
                self._m_recovered.inc()
            stream.seq = seq
            self._live[seq.request_id] = stream

    def _apply_migrate_out(self):
        """Driver-side eviction for live migration: displace each
        requested stream's sequence from this engine (chain donated,
        PRNG snapshotted — ``engine.evict``) and hand the pair to the
        fleet's ``handoff`` on this thread. A failed handoff (sibling
        draining) restores the sequence locally — a migration may be
        refused, but it may never lose a request."""
        while True:
            with self._lock:
                if not self._migrate_out:
                    return
                stream, handoff = self._migrate_out.popleft()
            seq = stream.seq
            if seq is None:
                # still in this gateway's intake (not yet admitted):
                # hand the bare request over instead
                with self._lock:
                    try:
                        self._intake.remove(stream)
                    except ValueError:
                        continue        # finished/cancelled/raced away
                    if stream._waiting:
                        self._backlog -= 1
                try:
                    handoff(stream, None)
                except Exception:
                    with self._lock:
                        if stream._waiting:
                            self._backlog += 1
                        self._intake.append(stream)
                continue
            if seq.done or self._live.get(seq.request_id) is not stream:
                continue                # finished, or already handed off
            if any(p is seq for p in self._parked) or (
                    self._suspect_ids
                    and seq.request_id in self._suspect_ids):
                continue                # mid-bisection: not migratable
            if seq.status != "queued" \
                    and not self._drain_supervised("evict"):
                # the fence faulted and the supervisor dealt with it: ask
                # again on the loop's next pass, of the engine that is
                # there then
                with self._lock:
                    self._migrate_out.appendleft((stream, handoff))
                return
            if not self.engine.evict(seq):
                continue
            del self._live[seq.request_id]
            self._probation.discard(seq.request_id)
            if stream._waiting:
                with self._lock:
                    self._backlog -= 1
            # the sibling's driver writes to the stream's sink from here
            # on: nothing of the stream may still lie in this batch
            self._hand_over()
            try:
                handoff(stream, seq)
            except Exception:
                # refused by the target: re-admit HERE by recompute —
                # the request stays live either way
                if stream._waiting:
                    with self._lock:
                        self._backlog += 1
                if self.engine.restore(seq):
                    self._m_recovered.inc()
                    self._live[seq.request_id] = stream

    def _apply_cancels(self):
        for stream in [s for s in self._live.values() if s._cancel]:
            seq = stream.seq
            parked = next((p for p in self._parked if p is seq), None)
            if parked is not None:
                # bisection-parked: not in any engine, cancel by hand —
                # honoring cancellation DURING recovery is part of the
                # fault-tolerance contract
                self._parked.remove(seq)
                seq.status = "finished"
                seq.finish_reason = "cancelled"
                self._on_finish(seq)
                continue
            if not seq.done and seq.status != "queued" \
                    and not self._drain_supervised("cancel"):
                return      # faulted: the flags stand, the next pass acts
            self.engine.cancel(seq)         # fires _on_finish

    def _drain_supervised(self, reason) -> bool:
        """Fence and accept the engine's program in flight under the
        supervisor, before a cancel or an eviction tears a slot down
        between two steps (the engine's own drain there is then a no-op).
        In steady decode a program is in flight nearly always, so this is
        the fence a client's disconnect meets: a device fault that
        surfaces at it is classified, retried or recovered from like one
        inside ``step()``, not the driver's death. False when it
        faulted: the caller leaves its request standing for the loop's
        next pass, which finds the pipeline empty or the engine
        rebuilt."""
        try:
            self.engine._drain(reason)
        except Exception as e:
            self._on_fault(e)
            return False
        return True

    def _sweep_parked_deadlines(self):
        """Bisection-parked sequences live outside the engine, so its
        per-step deadline sweep cannot see them — a parked request's
        ``timeout_s`` must still be honored here (deadlines share the
        engine's ``time.monotonic`` basis)."""
        if not self._parked:
            return
        now = time.monotonic()
        for seq in [p for p in self._parked
                    if p.deadline is not None and now >= p.deadline]:
            self._parked.remove(seq)
            seq.status = "finished"
            seq.finish_reason = "timeout"
            self._on_finish(seq)

    def _run(self):
        self.gc_watch.install()
        try:
            self._mark("loop")
            while True:
                self._arm_capture()
                self._admit_migrations()
                self._admit_intake()
                self._apply_cancels()
                self._apply_migrate_out()
                self._sweep_parked_deadlines()
                self._advance_bisection()
                if self.engine.has_work():
                    self._step_supervised()
                    continue
                self._mark("idle-wait")     # the idle wait is not the loop
                with self._lock:
                    drained = (not self._intake and not self._live
                               and not self._parked
                               and not self._migrate_in)
                    if self._closed and drained:
                        return
                # idle is provably not hung: refresh the watchdog
                # timestamp so last_step_age_s / the gauge measure
                # time-stuck-in-a-step, not time-without-traffic (an
                # orchestrator must not kill a healthy idle server)
                self._last_step_done = self._clock()
                self._wake.wait(self.idle_wait_s)
                self._mark("loop")
                self._wake.clear()
        except BaseException as e:
            # supervision exhausted (max_restarts, no factory, or a
            # non-Exception). FLEET FAILOVER first: offer every live
            # request — snapshotted exactly like a rebuild's recovery —
            # to the on_fatal hook, which re-admits them on a sibling
            # replica; only requests nobody adopted are stranded. The
            # driver is the only thread that can unblock consumers — it
            # must not strand them mid-result().
            handed = self._failover_handoff()
            with self._lock:
                self._closed = True
                stranded = (list(self._intake) + list(self._live.values())
                            + [st for st, _ in self._migrate_in])
                self._intake.clear()
                self._live.clear()
                self._parked.clear()
                self._migrate_in.clear()
            for s in stranded:
                if id(s) not in handed:
                    s._push_error(f"engine driver died: {e!r}", self._batch)
            self._hand_over()       # no mark follows: the thread ends here
            raise
        finally:
            self.gc_watch.remove()

    # ---------------------------------------------------------- supervisor
    def _step_supervised(self):
        """One engine step under supervision: classify any failure,
        retry transients with bounded backoff, rebuild + recover on
        fatal/hung, give up (re-raise, stranding with errors) only past
        ``max_restarts`` or without an ``engine_factory``."""
        t0 = self._clock()
        try:
            # a step that TRACED a new program (first hit of a prefill
            # bucket / decode geometry — routinely tens of seconds on a
            # real chip) is exempt from the watchdog: compile time is
            # not a hang, and classifying it as one would burn the
            # restart budget on healthy cold starts
            traces0 = (self.engine.decode_compilations()
                       + self.engine.prefill_compilations())
            self._mark("other")
            self.engine.step()
            # the driver's own work between two engine steps: the
            # checks below, then intake, cancels, deadlines, captures
            self._mark("loop", span=True)
            dt = self._clock() - t0
            compiled = (self.engine.decode_compilations()
                        + self.engine.prefill_compilations()) > traces0
            if self.watchdog_deadline_s is not None and not compiled \
                    and dt > self.watchdog_deadline_s:
                raise WatchdogTimeout(
                    f"engine step took {dt:.3f}s, watchdog deadline is "
                    f"{self.watchdog_deadline_s:.3f}s")
        except Exception as e:
            self._mark("loop")      # classification, backoff, a rebuild
            self._on_fault(e)
            return
        self._last_step_done = self._clock()
        self._transient_streak = 0
        self._tick_capture()
        if self._fault_at is not None:
            # first completed step on the rebuilt engine: recovery done
            self.restart_latencies.append(self._clock() - self._fault_at)
            self._fault_at = None
        if self._m_spec_len is not None:
            # drain the step's per-span acceptance lengths into the
            # histogram (driver thread is the only reader/writer)
            lens = self.engine.stats["spec_last_accept"]
            if lens:
                for m in lens:
                    self._m_spec_len.observe(m)
                self.engine.stats["spec_last_accept"] = []

    def _mark(self, phase, span=False):
        """The driver thread passes from one of the gateway's phases to
        another (``loop``, ``idle-wait``, or ``other`` on entering
        ``engine.step()``, which marks its own eight of the ten:
        ``engine._mark``). Ticks
        the driver clock, and at the same reading closes the ``loop``
        span on leaving the loop and, with ``span=True`` (a step just
        ended), opens it."""
        t = self.driver_clock.enter(phase)
        if phase != "loop":
            loop, self._loop_span = self._loop_span, None
            if loop is not None:
                loop.end(t1=t)
        elif span:
            tr = self._tr()
            if tr is not None:
                self._loop_span = tr.span("loop", tid=TID_GATEWAY, t0=t)

    def _classify(self, exc) -> str:
        if isinstance(exc, WatchdogTimeout):
            return "hung"
        if isinstance(exc, self.transient_types):
            return "transient"
        return "fatal"

    def _on_fault(self, exc):
        kind = self._classify(exc)
        self._m_faults.inc(kind=kind)
        # the supervisor keeps serving through a fault, so this is the one
        # place the error's own words (a Mosaic rejection, an out-of-memory
        # report) are written down before a rebuild hides them
        get_logger("serving").error("engine step fault (%s)", kind,
                                    exc_info=exc)
        tr = self._tr()
        if tr is not None:
            tr.instant(
                "fault", tid=TID_GATEWAY,
                args={"kind": kind, "error": type(exc).__name__,
                      "message": str(exc)[:200]})
        if self._fault_at is None:
            self._fault_at = self._clock()
        if kind == "transient":
            self._transient_streak += 1
            if self._transient_streak <= self.max_transient_retries:
                # retry the SAME engine: injected transients fire at a
                # step boundary, so engine bookkeeping is intact; real
                # ones (a flaky transfer) are worth one cheap retry
                # before paying a rebuild
                time.sleep(self.retry_backoff_s * self._transient_streak)
                return
            self._transient_streak = 0      # escalate: streak is a wedge
        if self.engine_factory is None or self._restarts >= self.max_restarts:
            raise exc
        self._rebuild_and_recover()

    @staticmethod
    def _snapshot_live(engine):
        """The recovery snapshot shared by crash-recovery rebuilds and
        fleet failover: every live slot-holder (arrival order) with a
        best-effort PRNG-walk snapshot — per-slot current keys, so
        sampled continuations restart mid-walk; unreadable device state
        (real crashes can corrupt it) only costs sampled-stream
        identity, recovery itself runs on host token state — plus the
        still-queued sequences. A step program still in flight is
        fenced and accepted first (its tokens are valid and the key state
        is ahead of the accepted tokens until they are); a fence that
        raises drops it, and the engine restores the keys itself.
        Returns ``(live, queued)``."""
        try:
            engine._drain("snapshot")
        except Exception:
            pass
        try:
            keys = np.asarray(engine._keys, np.uint32)
        except Exception:
            keys = None
        live = [s for s in engine._slots if s is not None and not s.done]
        live.sort(key=lambda s: s.request_id)   # arrival order
        for s in live:
            if keys is not None and s.tokens and s.status == "running" \
                    and s.slot is not None:
                s.key = keys[s.slot].copy()
        queued = [s for s in engine.scheduler.queue if not s.done]
        return live, queued

    def _failover_handoff(self) -> frozenset:
        """The dying driver's last act (fleet failover-to-sibling):
        snapshot every live request exactly like a rebuild's recovery
        would and offer the (stream, sequence) pairs to ``on_fatal``.
        The hook returning True means the fleet adopted them onto a
        sibling replica — those streams must NOT be stranded with
        errors. Returns the ids of handed-off streams (empty without a
        hook, on refusal, or if the handoff itself fails — stranding
        is the unchanged fallback)."""
        if self.on_fatal is None:
            return frozenset()
        try:
            live, queued = self._snapshot_live(self.engine)
            seqs = live + queued + [p for p in self._parked
                                    if not p.done]
            pairs, seen = [], set()
            for seq in seqs:
                st = self._live.get(seq.request_id)
                if st is not None and st.finish_reason is None \
                        and not st._cancel:
                    pairs.append((st, seq))
                    seen.add(id(st))
            with self._lock:
                pending = list(self._intake)
                migrating = list(self._migrate_in)
            for st in pending:
                if id(st) not in seen and st.finish_reason is None \
                        and not st._cancel:
                    pairs.append((st, None))
                    seen.add(id(st))
            for st, sq in migrating:
                if id(st) not in seen and st.finish_reason is None \
                        and not st._cancel:
                    pairs.append((st, sq))
                    seen.add(id(st))
            if pairs:
                self._hand_over()   # as before a migration's handoff
                res = self.on_fatal(self, pairs)
                if res is True:
                    return frozenset(id(st) for st, _ in pairs)
                if res:     # iterable of the streams actually adopted
                    return frozenset(id(st) for st in res)
        except Exception:
            pass        # failover is best-effort; stranding still works
        return frozenset()

    def _rebuild_and_recover(self):
        """Fatal-fault recovery: rebuild the engine and re-enqueue every
        live request by recompute — modulo the poison quarantine, which
        decides who re-enters now, who parks, and (once isolated) who
        is failed as the culprit."""
        self._recovering = True
        tr = self._tr()
        tr0 = tr.now() if tr is not None else None
        old = self.engine
        # bank the dead incarnation's counter stats so every derived
        # /metrics series stays monotonic (CARRIED_ENGINE_STATS). Built
        # aside and swapped in below WITH the new engine — one store —
        # so concurrent scrapes never see base and engine from
        # different epochs.
        live, queued = self._snapshot_live(old)     # drains: counts move
        base, pc_base, _ = self._counter_state
        new_base = {k: base[k] + old.stats[k]
                    for k in self._carried_stats}
        new = self.engine_factory()
        new.on_token = self._on_token
        new.on_finish = self._on_finish
        new.on_policy_preempt = self._on_policy_preempt
        new.on_step = self._on_step
        new.tracer = self.tracer     # one timeline across incarnations
        new.cost = self.cost         # one cost account, monotonic too
        new.driver_clock = self.driver_clock    # and one phase clock
        if self._fault_hook is not None:
            new.fault_hook = self._fault_hook
        new_pc = dict(pc_base)
        if old.prefix_cache is not None \
                and new.prefix_cache is not old.prefix_cache:
            # bank the dead trie's stats ONLY when the factory built a
            # fresh one (its stats restart at zero). An adopted SHARED
            # PrefixCache instance rides into the new engine with its
            # counts intact — banking those too would double them on
            # every restart.
            for k in CARRIED_PREFIX_STATS:
                new_pc[k] += old.prefix_cache.stats[k]
        self.engine = new
        self._counter_state = (new_base, new_pc, new)   # atomic swap
        self._restarts += 1
        self.last_restart_at = self._clock()    # the /debug/fleet column
        self._m_restarts.inc()
        readmit, culprit = self._quarantine_plan(live)
        recovered = 0
        for s in readmit + queued:
            if new.restore(s):
                self._m_recovered.inc()
                recovered += 1
        self._probation = {s.request_id for s in readmit + queued}
        if tr is not None:
            tr.complete("rebuild", tr0, tid=TID_GATEWAY,
                        args={"restarts": self._restarts,
                              "live": len(live), "queued": len(queued)})
            tr.instant("recovery", tid=TID_GATEWAY,
                       args={"recovered": recovered,
                             "parked": len(self._parked)})
        if culprit is not None:
            self._fail_poisoned(culprit)
        self._recovering = False

    def _quarantine_plan(self, live):
        """Split the recovered slot-holders into (readmit-now, culprit).
        First fault: readmit everyone (they enter probation). A repeat
        fault while probation members are still live starts the
        bisection: suspects are the probation members present at the
        fault; half readmit as the active set, half park. Conviction
        requires RECURRENCE UNDER ACTIVE BISECTION — a fault that
        follows a single-member active set is the poison (fail it,
        unpark everyone) — so two coincidental independent faults can
        shrink an innocent request to sole-suspect, but it is only
        failed if the fault then follows it a further time; otherwise
        it finishes and is exonerated."""
        bisecting = self._suspect_ids is not None
        watched = self._suspect_ids if bisecting else self._probation
        suspects = [s for s in live if s.request_id in watched]
        bystanders = [s for s in live if s.request_id not in watched]
        if not suspects:
            # fault not attributable to any prior readmission (fresh
            # fault, or suspects all finished): plain recovery
            self._suspect_ids = None
            return live, None
        if bisecting and len(suspects) == 1:
            # the fault followed this request through the halvings and
            # recurred on it alone — it is the poison. Everyone parked
            # re-enters.
            culprit = suspects[0]
            readmit = bystanders + self._parked
            self._parked = []
            self._suspect_ids = None
            return readmit, culprit
        half = (len(suspects) + 1) // 2
        active, benched = suspects[:half], suspects[half:]
        self._parked.extend(benched)
        self._suspect_ids = {s.request_id for s in active}
        tr = self._tr()
        if tr is not None:
            tr.instant(
                "bisection", tid=TID_GATEWAY,
                args={"verdict": "halved", "active": len(active),
                      "parked": len(benched)})
        return bystanders + active, None

    def _advance_bisection(self):
        """Driver-loop bookkeeping between steps: when the active
        suspect half has fully drained without re-faulting, it is
        exonerated — the culprit (if any) hides among the parked, so
        half of them re-enter as the next suspects. With nothing parked
        left, the bisection ends (the fault did not recur: poison gone,
        or it was step-pinned rather than request-pinned)."""
        if self._suspect_ids:
            return                  # active half still live — wait
        if not self._parked:
            self._suspect_ids = None
            return
        half = (len(self._parked) + 1) // 2
        batch, self._parked = self._parked[:half], self._parked[half:]
        batch = [s for s in batch if not s.done]
        tr = self._tr()
        if batch and tr is not None:
            tr.instant(
                "bisection", tid=TID_GATEWAY,
                args={"verdict": "reenter", "reentered": len(batch),
                      "parked": len(self._parked)})
        for s in batch:
            if self.engine.restore(s):
                self._m_recovered.inc()
        ids = {s.request_id for s in batch}
        self._suspect_ids = ids if (ids or self._parked) else None
        self._probation |= ids

    def _fail_poisoned(self, seq):
        """Terminate the isolated culprit — the ONLY request a poison
        fault costs. Consumers see ``finish_reason="error"``: SSE gets
        a terminal error event, blocking a JSON 500."""
        seq.status = "finished"
        seq.finish_reason = "error"
        tr = self._tr()
        if tr is not None:
            tr.instant(
                "bisection", tid=TID_GATEWAY,
                args={"verdict": "poisoned",
                      "request_tid": tr.req_tid(seq.request_id)})
            tr.instant("finished", tid=tr.req_tid(seq.request_id),
                       args={"finish_reason": "error"})
        stream = self._finish_teardown(seq)
        if stream is not None:
            stream._push_error(
                "poisoned request: engine fault recurred pinned to this "
                "request; bystanders recovered", self._batch)

    # ----------------------------------------------------- trace capture
    def _arm_capture(self):
        """Driver-side capture start: a pending window opens at a STEP
        BOUNDARY (top of the driver loop), never mid-step — so every
        step the countdown charges was recorded from its first event
        and the capture holds exactly the asked-for step spans. Arming
        runs under the gateway lock so it cannot race the handler's
        timeout cleanup — an orphaned window must never enable the
        tracer with nobody left to read or stop it."""
        if self._capture is None and self._pcapture is None:
            return                      # lock-free fast path
        with self._lock:
            cap = self._capture
            if cap is not None and not cap["armed"]:
                self.tracer.clear()
                self.tracer.enable()
                cap["armed"] = True
            pc = self._pcapture
            if pc is not None and not pc["armed"] \
                    and self.cost is not None:
                # profile window base: the accounting as of this step
                # boundary — the returned table is exactly the next
                # ``steps`` steps' worth of cost
                pc["base"] = self._profile_snapshot()
                pc["armed"] = True

    def _tick_capture(self):
        """Driver-side capture countdown: called after every completed
        supervised step. When the requested window closes, recording
        stops (unless tracing is persistent) so the capture holds
        exactly the asked-for steps, and the waiting handler wakes.
        Locked for the same reason as :meth:`_arm_capture`; the
        no-capture fast path stays one attribute check."""
        if self._capture is None and self._pcapture is None:
            return                      # lock-free fast path
        with self._lock:
            cap = self._capture
            if cap is not None and cap["armed"]:
                cap["remaining"] -= 1
                if cap["remaining"] <= 0:
                    if not self.trace_persistent:
                        self.tracer.disable()
                    cap["done"].set()
            pc = self._pcapture
            if pc is not None and pc["armed"]:
                pc["remaining"] -= 1
                if pc["remaining"] <= 0 and pc["end"] is None:
                    # freeze the window's END at this exact step
                    # boundary: the driver keeps stepping while the
                    # waiting handler wakes, and those later steps
                    # must not leak into the N-step document
                    pc["end"] = self._profile_snapshot()
                    pc["done"].set()

    def capture_trace(self, steps=32, timeout_s=30.0, xplane=False):
        """Capture ``steps`` engine steps of trace and return the
        Chrome trace document (the ``GET /debug/trace`` body).

        ``xplane=True`` (``GET /debug/xplane``) runs the JAX profiler
        over the same steps, into a new directory named by the
        document's ``otherData["xplane_dir"]``: the device's ops, and
        on the host plane the engine- and gateway-lane spans of this
        document on the device's clock. A profiler session someone else
        holds is a busy capture too.

        ``steps <= 0`` snapshots the current buffer without touching
        recording state — the natural read when tracing is persistent
        (``trace=True`` / ``--trace``). Otherwise the buffer is
        cleared, recording turns on, and the call blocks until the
        driver completes ``steps`` steps or ``timeout_s`` elapses (an
        idle engine steps nothing — the timeout returns whatever was
        captured, e.g. only gateway events). Captures serialize:
        a second concurrent capture raises :class:`TraceBusyError`.
        Safe from any thread; the driver's arming/countdown and this
        teardown all run under the gateway lock."""
        tr = self.tracer
        if steps <= 0:
            return tr.export()
        # clamp: Event.wait overflows on absurd timeouts, and a capture
        # that outlives any plausible debugging session is a leak
        timeout_s = min(max(float(timeout_s), 0.0), 3600.0)
        with self._lock:
            if self._capture is not None:
                raise TraceBusyError(
                    "a trace capture is already in progress")
            done = threading.Event()
            self._capture = {"remaining": int(steps), "done": done,
                             "armed": False}
        xplane_dir = None
        try:
            if xplane:
                xplane_dir = tempfile.mkdtemp(prefix="xplane-")
                try:
                    jax.profiler.start_trace(xplane_dir)
                except RuntimeError as e:
                    os.rmdir(xplane_dir)
                    xplane_dir = None
                    raise TraceBusyError(
                        f"the JAX profiler is already tracing: {e}")
            self._wake.set()
            done.wait(timeout_s)
        finally:
            # unconditional teardown: an exception here must not leave
            # an orphaned window 409-ing every later capture (or the
            # tracer recording with nobody left to stop it)
            with self._lock:
                cap, self._capture = self._capture, None
                if cap is not None and cap["armed"] \
                        and not self.trace_persistent:
                    tr.disable()
            if xplane_dir is not None:
                jax.profiler.stop_trace()
        doc = tr.export()
        if xplane_dir is not None:
            doc["otherData"]["xplane_dir"] = xplane_dir
        return doc

    # ------------------------------------------------------ cost profile
    def _profile_snapshot(self) -> dict:
        """One consistent reading of the accounting + token count (the
        base or frozen end of a step-bounded window)."""
        return {"cost": self.cost.snapshot_full(),
                "tokens": self._stat("tokens_generated")}

    def profile_doc(self, base=None, window_steps=None, at=None) -> dict:
        """The cost-attribution document (the ``GET /debug/profile``
        body): per-program calls / transfer bytes / compile events /
        wall EWMA / share of the window's wall, phase attribution, and
        the per-decoded-token rates the mega-kernel work is gated on.
        ``base``/``at`` bound the window (prior
        :meth:`_profile_snapshot` readings; None = gateway start /
        now)."""
        co = self.cost
        if co is None:
            raise RuntimeError(
                "cost observatory disabled (gateway built with "
                "cost=False)")
        doc = co.export(base=(base or {}).get("cost"),
                        at=(at or {}).get("cost"))
        tokens = ((at["tokens"] if at is not None
                   else self._stat("tokens_generated"))
                  - (base or {}).get("tokens", 0))
        t = doc["totals"]
        t["decoded_tokens"] = tokens
        t["dispatches_per_decoded_token"] = round(
            t["dispatches"] / max(tokens, 1), 6)
        t["h2d_bytes_per_decoded_token"] = round(
            t["h2d_bytes"] / max(tokens, 1), 3)
        t["d2h_bytes_per_decoded_token"] = round(
            t["d2h_bytes"] / max(tokens, 1), 3)
        doc["window_steps"] = window_steps
        eng = self.engine
        # KV columns in BYTES, not blocks (README "Quantized
        # serving"): block counts hide the density story — an int8
        # pool's block is ~4x smaller — so the profile reports the
        # dtype-aware byte footprint (live/trie split from
        # occupancy(), per-block bytes from the pool) alongside
        # the storage dtype and per-token rate.
        # ONE occupancy walk: every byte field below derives from
        # this reading plus the pool's per-block constants
        occ = eng.cache.occupancy()
        kv_b = eng.cache.pool.block_nbytes
        sc_b = eng.cache.pool.scale_block_nbytes
        per_block = kv_b + sc_b
        used = occ["live"] + occ["trie"]
        doc["kv_pool"] = {
            "kv_dtype": eng.kv_dtype,
            # the other two low-precision knobs ride along so the
            # whole "Quantized serving" posture reads off one block
            "quantize_weights": getattr(eng, "quantize_weights",
                                        False),
            "quantize_activations": getattr(
                eng, "quantize_activations", False),
            "live_bytes": occ["live"] * per_block,
            "trie_bytes": occ["trie"] * per_block,
            "free_bytes": occ["free"] * per_block,
            "used_kv_bytes": used * kv_b,
            "used_scale_bytes": used * sc_b,
            "capacity_bytes": eng.cache.pool.num_blocks * per_block,
            "bytes_per_token": eng.cache.bytes_per_token(),
        }
        if getattr(eng, "tp", 1) > 1:
            # per-layer collective-bytes column (README "Tensor-
            # parallel serving"): annotate the window's all-reduce
            # wire traffic (already delta'd by co.export) per layer
            # and per decoded token, so the EQuARX int8 win reads
            # directly off the profile
            L = max(int(eng.config.num_hidden_layers), 1)
            doc["collectives"] = {
                "tp": eng.tp,
                "per_dtype": {
                    dtype: dict(
                        rec,
                        bytes_per_layer=round(rec["bytes"] / L, 3),
                        bytes_per_decoded_token=round(
                            rec["bytes"] / max(tokens, 1), 3))
                    for dtype, rec in doc.get("collectives", {}).items()
                },
            }
        pc = getattr(eng, "prefix_cache", None)
        if pc is not None and pc.tier is not None:
            # tier columns (README "Tiered KV prefix cache"): the
            # window's spill/readmit/peer traffic (already delta'd by
            # co.export) annotated per decoded token, plus the tier's
            # current occupancy — so tier pressure reads directly off
            # the profile without touching the per-program baselines
            doc["tiers"] = {
                "host_tier_bytes": pc.host_tier_bytes,
                "tier_blocks": pc.tier.num_blocks,
                "tier_bytes": pc.tier.bytes_used,
                "per_direction": {
                    d: dict(
                        rec,
                        bytes_per_decoded_token=round(
                            rec["bytes"] / max(tokens, 1), 3))
                    for d, rec in doc.get("tiers", {}).items()
                },
            }
        return doc

    def capture_profile(self, steps=0, timeout_s=30.0) -> dict:
        """Aggregate cost attribution (``steps <= 0``: everything since
        gateway start), or a STEP-BOUNDED window: block until the
        driver completes ``steps`` engine steps and return only that
        window's costs — the same arm-at-a-step-boundary /
        count-completed-steps machinery as :meth:`capture_trace`, and
        the same serialization rule (a second concurrent window raises
        :class:`TraceBusyError` → HTTP 409)."""
        if self.cost is None:
            raise RuntimeError(
                "cost observatory disabled (gateway built with "
                "cost=False)")
        if steps <= 0:
            return self.profile_doc()
        timeout_s = min(max(float(timeout_s), 0.0), 3600.0)
        with self._lock:
            if self._pcapture is not None:
                raise TraceBusyError(
                    "a profile capture is already in progress")
            done = threading.Event()
            self._pcapture = {"remaining": int(steps), "done": done,
                              "armed": False, "base": None,
                              "end": None, "steps": int(steps)}
        try:
            self._wake.set()
            done.wait(timeout_s)
        finally:
            with self._lock:
                pc, self._pcapture = self._pcapture, None
                if pc["end"] is None:
                    # timed out mid-window: freeze the end NOW, under
                    # the lock, so it is consistent with `remaining`
                    pc["end"] = self._profile_snapshot()
        # report the steps the window actually captured, not the ask: a
        # timed-out capture (slow engine, or a window that never armed
        # because the driver is idle/dead) must not label lifetime or
        # partial totals as an N-step window — per-step rates derived
        # from the document would be silently off. A never-armed window
        # captured NOTHING: its base is its end (empty deltas), never
        # the lifetime aggregate with a 0-step label.
        armed = pc["base"] is not None
        completed = (min(pc["steps"] - max(pc["remaining"], 0),
                         pc["steps"]) if armed else 0)
        doc = self.profile_doc(base=pc["base"] if armed else pc["end"],
                               window_steps=completed, at=pc["end"])
        doc["window_steps_requested"] = pc["steps"]
        doc["window_truncated"] = completed < pc["steps"]
        return doc

    # ------------------------------------------------------ debug surface
    def request_table(self) -> list:
        """Live request table (the ``GET /debug/requests`` body): one
        row per in-flight request — state, slot, token progress,
        queue-wait, TTFT, TPOT-so-far and KV footprint. Reads host
        bookkeeping the driver thread writes (ints/short lists under
        the GIL — same discipline as the scrape-time gauges)."""
        eng = self.engine
        now = eng._clock()
        with self._lock:
            pending = list(self._intake)
            live = list(self._live.values())
        parked_ids = {id(p) for p in self._parked}
        rows = []
        wall = time.monotonic()
        for st in pending:
            # class + TTFT-deadline slack (README "Multi-tenant SLO
            # serving"): pending requests resolve against the live
            # class table (they passed validate at submit, so this
            # cannot raise); slack counts down on the same wall wait
            # the row's queue_wait_s shows
            pclass = eng.classes.resolve(st.request.priority_class)
            slack = (None if pclass.ttft_slo_s is None else
                     round(pclass.ttft_slo_s - (wall - st.submit_time), 6))
            rows.append({"id": st.id, "state": "pending", "slot": None,
                         "class": pclass.name,
                         "prompt_tokens": len(st.request.prompt),
                         "generated_tokens": 0,
                         "max_new_tokens": int(st.request.max_new_tokens),
                         # wait-so-far on the gateway wall clock (the
                         # engine has not seen this request yet, so no
                         # engine-clock stamp exists) — the longest
                         # waiters are exactly the rows an operator
                         # inspecting a saturated server looks for
                         "queue_wait_s": round(wall - st.submit_time, 6),
                         "ttft_s": None,
                         "tpot_s": None, "kv_tokens": 0,
                         "kv_blocks": None,
                         "launches": 0, "kv_bytes": 0,
                         "slo_slack_s": slack})
        for st in live:
            seq = st.seq
            slot = seq.slot
            qw = seq.queue_wait_s
            if qw is None and seq.t_submit is not None:
                qw = now - seq.t_submit          # still waiting: so far
            tpot = seq.tpot_s
            if tpot is None and seq.t_first_token is not None \
                    and len(seq.tokens) > 1 \
                    and seq.t_last_token is not None:
                # TPOT-so-far from the LAST ACCEPTED token's stamp, not
                # the live clock: mid-step the token count is frozen at
                # the previous host-accept while `now` keeps advancing,
                # so a clock-based numerator inflates for the whole
                # step — n ticks of it under multi-tick decode — then
                # snaps back. Stamp-over-stamp stays consistent however
                # long the device runs between syncs.
                tpot = (seq.t_last_token - seq.t_first_token) \
                    / (len(seq.tokens) - 1)
            kv_tokens, kv_blocks, kv_bytes = 0, None, 0
            if slot is not None:
                kv_tokens = int(eng.cache.lengths[slot])
                kv_bytes = eng.cache.slot_kv_bytes(slot)
                kv_blocks = len(eng.cache.slot_block_ids(slot))
            # TTFT-deadline slack on the engine clock: settled once the
            # first token landed (negative = the miss already counted),
            # counting down from the wait-so-far while still queued
            pclass = seq.pclass
            slack = None
            if pclass is not None and pclass.ttft_slo_s is not None:
                waited = seq.ttft_s
                if waited is None and seq.t_submit is not None:
                    waited = now - seq.t_submit
                if waited is not None:
                    slack = round(pclass.ttft_slo_s - waited, 6)
            rows.append({
                "id": st.id,
                "state": ("parked" if id(seq) in parked_ids
                          else seq.status),
                "slot": slot,
                "class": (pclass.name if pclass is not None
                          else eng.classes.default),
                "prompt_tokens": seq.prompt_len,
                "generated_tokens": len(seq.tokens),
                "max_new_tokens": int(seq.request.max_new_tokens),
                "queue_wait_s": None if qw is None else round(qw, 6),
                "ttft_s": (None if seq.ttft_s is None
                           else round(seq.ttft_s, 6)),
                "tpot_s": None if tpot is None else round(tpot, 6),
                "kv_tokens": kv_tokens,
                "kv_blocks": kv_blocks,
                # cost columns (README "Cost attribution &
                # /debug/profile"): device launches this request has
                # ridden so far, and the HBM bytes its KV currently
                # holds (blocks x block bytes)
                "launches": seq.launches,
                "kv_bytes": kv_bytes,
                "slo_slack_s": slack,
            })
        return rows

    # ------------------------------------------------------ health surface
    @property
    def running_slots(self) -> int:
        """Slots actively decoding (the ``/healthz`` saturation view)."""
        return sum(1 for s in self.engine._slots
                   if s is not None and s.status == "running")

    @property
    def prefilling_slots(self) -> int:
        """Slots held by mid-chunked-prefill sequences."""
        return sum(1 for s in self.engine._slots
                   if s is not None and s.status == "prefilling")

    @property
    def restarts(self) -> int:
        return self._restarts

    def last_step_age(self) -> float:
        """Seconds since the last completed engine step (the watchdog's
        external visibility — grows without bound while a step is hung)."""
        return max(0.0, self._clock() - self._last_step_done)

    @property
    def health_state(self) -> str:
        """``ok`` | ``degraded`` | ``recovering`` | ``draining`` — the
        ``/healthz`` status. ``recovering``: an engine rebuild or a
        poison bisection is in progress (parked requests exist or a
        suspect half is live). ``degraded``: serving, but the last
        recovery's readmissions have not all finished yet (probation)
        or a transient-retry streak is active."""
        if self._closed:
            return "draining"
        if self._recovering or self._parked or self._suspect_ids:
            return "recovering"
        if self._probation or self._transient_streak:
            return "degraded"
        return "ok"

    # ------------------------------------------------------------ shutdown
    def shutdown(self, drain=True, timeout=None):
        """Close the front door; ``drain=True`` lets in-flight and
        queued work finish, ``drain=False`` cancels it. Blocks until the
        driver exits (or ``timeout``). Returns True if it did."""
        with self._lock:
            self._closed = True
            streams = ([] if drain else
                       list(self._intake) + list(self._live.values()))
        for s in streams:
            s._cancel = True
        self._wake.set()
        if self._thread.is_alive():
            self._thread.join(timeout)
        atexit.unregister(self._atexit_hook)
        return not self._thread.is_alive()
