"""Step programs: device idle time under the ``call`` span (the jitted call
alone, inside ``dispatch``), per traced step; ``idle_in_dispatch_ms_per_step``
less this is the idle time under the commit that follows the call."""
import stall_trace


def reduce(src):
    return stall_trace.idle_ms_per_step(src, "call")
