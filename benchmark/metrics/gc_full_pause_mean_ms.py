"""Gateway: mean pause of a full collection (generation 2 of
``serving_gc_pause_seconds_total`` over ``serving_gc_collections_total``);
0.0 where the window held none."""
import stall_trace


def reduce(src):
    return stall_trace.gc_full_pause_mean_ms(src)
