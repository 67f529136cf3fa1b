"""Gateway: per step, wall less CPU seconds of every phase but
``device-wait`` and ``idle-wait`` (``serving_driver_seconds_total``): the
driver thread runnable or blocked and not computing, while it had work of
its own (the GIL handed to the request handlers' threads, a blocking
transfer inside ``dispatch``)."""
import driver_clock


def reduce(src):
    w = driver_clock.window(src)
    if not w or not w[1]:
        return None
    off = driver_clock.busy_s(w[0]) - driver_clock.busy_s(w[0], "cpu")
    return 1e3 * off / w[1]
