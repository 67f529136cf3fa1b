"""Step programs: wall time of the ``dispatch`` phase per step
(``serving_driver_seconds_total``): the jitted call of the step's program
until it returns, and the bookkeeping of what it advances."""
import driver_clock


def reduce(src):
    return driver_clock.ms_per_step(src, "dispatch")
