"""Gateway: seconds the collector stopped the process per step
(``serving_gc_pause_seconds_total``, every generation), with no tracer."""
import stall_trace


def reduce(src):
    return stall_trace.gc_pause_ms_per_step(src)
