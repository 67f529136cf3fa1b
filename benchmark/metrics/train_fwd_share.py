"""Trainer: device time of the forward pass: ``jvp(...)`` in the op's
name, outside any ``transpose``,
over that of all ops in the trace; with the other three phases and what
carries no such name ("other") it sums to 100."""
import timeline


def reduce(src):
    return timeline.phase_share(src, "fwd")
