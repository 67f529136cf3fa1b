"""Device: device idle time under ``gc`` spans (a collection, on whichever
thread it ran), per traced step. A second axis: ``gc`` overlaps the engine's
spans and does not partition with them."""
import stall_trace


def reduce(src):
    return stall_trace.idle_ms_per_step(src, "gc")
