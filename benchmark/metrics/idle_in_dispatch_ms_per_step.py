"""Step programs: device idle time under the ``dispatch`` span (the jitted
call, from its arguments' transfer until it returns), per traced step."""
import timeline


def reduce(src):
    return timeline.idle_ms_per_step(src, ("dispatch",))
