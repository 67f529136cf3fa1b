"""Step programs: the driver thread's own work per step, with no tracer and
no profiler: wall seconds of every phase of ``serving_driver_seconds_total``
but ``device-wait`` and ``idle-wait``, over the steps of the window."""
import driver_clock


def reduce(src):
    w = driver_clock.window(src)
    if not w or not w[1]:
        return None
    return 1e3 * driver_clock.busy_s(w[0]) / w[1]
