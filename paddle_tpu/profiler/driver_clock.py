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

The gateway owns one instance across engine rebuilds, as it owns the tracer
and the cost observatory, and exports it as
``serving_driver_seconds_total{phase, clock}``. Only the driver thread
writes; a scrape reads floats.
"""
from __future__ import annotations

import time

#: ``loop``: the gateway between two steps (intake, cancels, deadlines,
#: captures, supervision); ``idle-wait``: waiting for work; ``admit`` /
#: ``plan`` / ``dispatch`` / ``device-wait`` / ``host-accept``: the engine's
#: spans of the same names; ``other``: inside ``step()`` and under none of
#: those (deadline sweep, dispatch args, step accounting, counter samples)
PHASES = ("loop", "idle-wait", "admit", "plan", "dispatch", "device-wait",
          "host-accept", "other")


class DriverClock:
    """``wall`` is any zero-arg seconds callable (the gateway passes its
    injectable clock), ``cpu`` any zero-arg nanoseconds callable of the
    calling thread's CPU time. ``stamps_spans``: whether the tracer whose
    spans open and close at the marks reads ``wall`` too, so that a mark's
    reading may stand for the span's own."""

    def __init__(self, wall=None, cpu=None, stamps_spans=True):
        self.wall = wall if wall is not None else time.perf_counter
        self.cpu = cpu if cpu is not None else time.thread_time_ns
        self.stamps_spans = bool(stamps_spans)
        self.phase = None           # None until the first mark
        self.wall_s = dict.fromkeys(PHASES, 0.0)
        self.cpu_ns = dict.fromkeys(PHASES, 0)
        self._t = 0.0
        self._c = 0

    def enter(self, phase):
        """Leave the current phase for ``phase``; returns the wall reading
        for a span opened or closed at this boundary to carry (None where
        the tracer is on a clock of its own and reads that)."""
        t, c = self.wall(), self.cpu()
        cur = self.phase
        if cur is not None:
            self.wall_s[cur] += t - self._t
            self.cpu_ns[cur] += c - self._c
        self.phase, self._t, self._c = phase, t, c
        return t if self.stamps_spans else None

    def seconds(self, phase, clock):
        """Seconds charged to ``phase`` so far on ``clock`` (``wall`` or
        ``cpu``); the phase in progress counts from its next mark."""
        if clock == "wall":
            return self.wall_s[phase]
        return self.cpu_ns[phase] / 1e9
