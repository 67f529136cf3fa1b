"""Scheduler: device idle time under the engine's ``admit`` and ``plan``
spans, per traced step: what admission and packing the step cost the chip."""
import timeline


def reduce(src):
    return timeline.idle_ms_per_step(src, ("admit", "plan"))
