"""Step programs: device idle time under the ``host-accept`` span (token and
chunk bookkeeping after the fence), per traced step."""
import timeline


def reduce(src):
    return timeline.idle_ms_per_step(src, ("host-accept",))
