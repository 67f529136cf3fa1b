"""Scheduler: wall time of the ``admit`` phase per step
(``serving_driver_seconds_total``): whole-prompt prefill of the admitted
group and the fences it waits on."""
import driver_clock


def reduce(src):
    return driver_clock.ms_per_step(src, "admit")
