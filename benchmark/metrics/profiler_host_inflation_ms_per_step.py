"""Device: what the profiler adds to the host's step: ``host_busy_ms_per_step``
between the two scrapes that bracket the device trace less the same over the
rest of the window. It says how far ``device_idle_share`` and the
``idle_in_*`` family, read from the traced seconds, describe the program
the end-to-end metric measures."""
import driver_clock


def reduce(src):
    both = driver_clock.busy_ms_per_step_inside_and_outside(src)
    return both[0] - both[1] if both else None
