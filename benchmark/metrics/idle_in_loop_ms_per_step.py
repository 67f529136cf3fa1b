"""Gateway: device idle time under the driver's ``loop`` span (from the end
of one ``engine.step()`` to the start of the next: intake, cancels,
deadlines, capture ticks, the step histogram), per traced step."""
import timeline


def reduce(src):
    return timeline.idle_ms_per_step(src, ("loop",))
