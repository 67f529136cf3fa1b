"""Step programs: device idle time under the ``retire`` span (from the end
of ``host-accept`` to the end of the step), per traced step."""
import stall_trace


def reduce(src):
    return stall_trace.idle_ms_per_step(src, "retire")
