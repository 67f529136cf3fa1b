"""Scheduler: device idle time under the ``sweep`` span (the step's start,
until ``admit`` or ``plan``), per traced step."""
import stall_trace


def reduce(src):
    return stall_trace.idle_ms_per_step(src, "sweep")
