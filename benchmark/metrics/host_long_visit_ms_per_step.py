"""Step programs: wall time per step in visits to one phase longer than 8 ms
(``serving_driver_long_visit_seconds_total``), every phase but
``device-wait`` and ``idle-wait``: the host's stalls, with no tracer."""
import stall_trace


def reduce(src):
    return stall_trace.host_long_visit_ms_per_step(src)
