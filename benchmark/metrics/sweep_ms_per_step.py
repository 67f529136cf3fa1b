"""Scheduler: wall time of the ``sweep`` phase per step
(``serving_driver_seconds_total``): the step's start, until ``admit`` or
``plan``: the deadline sweep, policy preemption, the scheduler's admissions
with the prefix cache's hit lengths. Part of ``other`` before PR 52."""
import driver_clock


def reduce(src):
    return driver_clock.ms_per_step(src, "sweep")
