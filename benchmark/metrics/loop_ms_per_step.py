"""Gateway: wall time of the ``loop`` phase per step
(``serving_driver_seconds_total``): the gateway's work between two steps
(intake, cancels, deadlines, captures, supervision)."""
import driver_clock


def reduce(src):
    return driver_clock.ms_per_step(src, "loop")
