"""Trainer: device time of the optimizer update (``TrainStep``'s ``optimizer``
scope in the op's name),
over that of all ops in the trace; with the other three phases and what
carries no such name ("other") it sums to 100."""
import timeline


def reduce(src):
    return timeline.phase_share(src, "opt")
