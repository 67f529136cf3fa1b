"""Device: device idle time in gaps of 5 ms or more that lies under none of
``admit``, ``plan``, ``sweep``, ``call``, ``dispatch``, ``device-wait``,
``host-accept``, ``retire``, ``loop``, ``gc``, per traced step: the long
stalls that still have no name."""
import stall_trace


def reduce(src):
    return stall_trace.long_gap_unnamed_ms_per_step(src)
