"""Where the serving driver thread's time goes, phase by phase and always on
(README "Tracing & debugging").

The thread that runs the gateway's loop and ``engine.step()`` under it is at
every moment in exactly one phase of :data:`PHASES`. A mark,
:meth:`DriverClock.enter`, reads the wall clock and the thread's CPU clock
once, charges what elapsed to the phase being left, and makes the new phase
current: the phases partition the thread's time by construction, so summed
over phases the wall time equals the time since the first mark. Wall less
CPU of a phase is the time the thread was runnable or blocked and not
computing (the GIL held by another thread, a blocking transfer).

A visit to one phase, mark to mark, that lasts longer than
:data:`LONG_VISIT_S` is counted by its phase besides: the sums say that
``dispatch`` costs 2.6 ms a step, the long visits that it once cost 107.

The gateway owns one instance across engine rebuilds, as it owns the tracer
and the cost observatory, and exports it as
``serving_driver_seconds_total{phase, clock}``,
``serving_driver_long_visits_total{phase}`` and
``serving_driver_long_visit_seconds_total{phase}``. Only the driver thread
writes; a scrape reads floats.
"""
from __future__ import annotations

import time

#: ``loop``: the gateway between two steps (intake, cancels, deadlines,
#: captures, supervision); ``idle-wait``: waiting for work; ``sweep`` /
#: ``admit`` / ``plan`` / ``dispatch`` / ``device-wait`` / ``host-accept`` /
#: ``retire``: the engine's spans of the same names (``sweep``: the step's
#: start, deadlines, policy and the scheduler's admissions; ``retire``: step
#: accounting, ``on_step``, a traced step's counter samples, the return);
#: ``other``: inside ``step()`` and under none of those (a traced step's
#: dispatch args, the exception paths)
PHASES = ("loop", "idle-wait", "sweep", "admit", "plan", "dispatch",
          "device-wait", "host-accept", "retire", "other")
#: a visit to one phase longer than this is a stall, counted where it
#: happens: 2.5 x the largest busy phase's mean in any cell of the benchmark
#: and under its shortest device step (10.7 ms), so a host visit that long
#: empties the one-deep pipeline everywhere
LONG_VISIT_S = 0.008


class DriverClock:
    """``wall`` is any zero-arg seconds callable (the gateway passes its
    injectable clock), ``cpu`` any zero-arg nanoseconds callable of the
    calling thread's CPU time. ``stamps_spans``: whether the tracer whose
    spans open and close at the marks reads ``wall`` too, so that a mark's
    reading may stand for the span's own. ``on_mark``: called at every mark
    before the clocks are read, so that what it does is charged to the
    phase being left (the gateway hands a step's stream events over there)."""

    def __init__(self, wall=None, cpu=None, stamps_spans=True, on_mark=None):
        self.wall = wall if wall is not None else time.perf_counter
        self.cpu = cpu if cpu is not None else time.thread_time_ns
        self.stamps_spans = bool(stamps_spans)
        self.on_mark = on_mark
        self.phase = None           # None until the first mark
        self.wall_s = dict.fromkeys(PHASES, 0.0)
        self.cpu_ns = dict.fromkeys(PHASES, 0)
        #: visits longer than :data:`LONG_VISIT_S`, and their wall seconds
        self.long_visits = dict.fromkeys(PHASES, 0)
        self.long_visit_s = dict.fromkeys(PHASES, 0.0)
        self._t = 0.0
        self._c = 0

    def enter(self, phase):
        """Leave the current phase for ``phase``; returns the wall reading
        for a span opened or closed at this boundary to carry (None where
        the tracer is on a clock of its own and reads that)."""
        if self.on_mark is not None:
            self.on_mark()
        t, c = self.wall(), self.cpu()
        cur = self.phase
        if cur is not None:
            dt = t - self._t
            self.wall_s[cur] += dt
            self.cpu_ns[cur] += c - self._c
            if dt > LONG_VISIT_S:
                self.long_visits[cur] += 1
                self.long_visit_s[cur] += dt
        self.phase, self._t, self._c = phase, t, c
        return t if self.stamps_spans else None

    def seconds(self, phase, clock):
        """Seconds charged to ``phase`` so far on ``clock`` (``wall`` or
        ``cpu``); the phase in progress counts from its next mark."""
        if clock == "wall":
            return self.wall_s[phase]
        return self.cpu_ns[phase] / 1e9
