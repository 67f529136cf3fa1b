"""Step programs: wall time of the ``retire`` phase per step
(``serving_driver_seconds_total``): from the end of ``host-accept`` to the
gateway's ``loop`` mark: step accounting, ``on_step``, the frames' teardown,
a traced step's counter samples. Part of ``other`` before PR 52; what
handing the step's tokens over after ``dispatch`` would move."""
import driver_clock


def reduce(src):
    return driver_clock.ms_per_step(src, "retire")
