"""The collector's pauses, counted always and spanned when the tracer
records (README "Tracing & debugging").

A collection stops every Python thread of the process for as long as it
runs, whichever thread's allocation set it off, and a full one over the
heap of a serving process (the jaxprs and lowered modules of every program
it warmed) can take as long as several device steps. :class:`GcWatch` is a
callback on ``gc.callbacks``: at ``start`` and ``stop`` it reads the wall
clock it was given and adds the pause to its generation's sum, and while the
tracer records on the machine's clock it opens and closes a ``gc`` span on
:data:`~.tracing.TID_GC` (mirrored into the device trace like every engine-
and gateway-lane span, on the collecting thread). Under an injected clock it
records nothing: when the collector runs is no function of a replay's
inputs, and a replayed capture must stay byte-identical.

The gateway owns one instance across engine rebuilds and exports it as
``serving_gc_pause_seconds_total{generation}`` and
``serving_gc_collections_total{generation}``; it is installed while the
driver thread runs. Collections do not overlap, so one open reading is all
the state there is.
"""
from __future__ import annotations

import gc

from .tracing import TID_GC

GENERATIONS = (0, 1, 2)


class GcWatch:
    """``wall`` is any zero-arg seconds callable (the gateway's clock);
    ``tracer`` a :class:`~.tracing.SpanTracer` or None."""

    def __init__(self, wall, tracer=None):
        self.wall = wall
        self.tracer = tracer
        self.collections = dict.fromkeys(GENERATIONS, 0)
        self.pause_s = dict.fromkeys(GENERATIONS, 0.0)
        self._t0 = None
        self._span = None

    def install(self):
        if self not in gc.callbacks:
            gc.callbacks.append(self)
        return self

    def remove(self):
        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def __call__(self, phase, info):
        if phase == "start":
            tr = self.tracer
            if tr is not None and tr.enabled and tr.real_clock:
                self._span = tr.span(
                    "gc", tid=TID_GC, args={"generation": info["generation"]})
            self._t0 = self.wall()
        elif self._t0 is not None:      # installed between start and stop
            dt = self.wall() - self._t0
            self._t0 = None
            gen = info["generation"]
            self.collections[gen] += 1
            self.pause_s[gen] += dt
            span, self._span = self._span, None
            if span is not None:
                span.end({"collected": info["collected"]})
