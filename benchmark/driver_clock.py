"""The driver thread's phase clock as the metric readers read it:
``serving_driver_seconds_total{phase, clock}`` (always on; the gateway's
``/metrics``), the seconds the thread that runs the gateway's loop and
``engine.step()`` spent in each of eight phases that partition its time, on
the wall clock and on its own CPU clock. Steps are the delta of
``serving_step_duration_seconds_count``.

Every function returns None where a scrape lacks the family (a program from
before the clock, a training run) and raises nothing.
"""
import re

FAMILY = "serving_driver_seconds_total"
STEPS = "serving_step_duration_seconds_count"
#: the phases in which the host does no work of its own: it waits for the
#: chip, or for a request
WAITING = ("device-wait", "idle-wait")
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def _seconds(scrape):
    """{(phase, clock): seconds} of one scrape."""
    vals = scrape.get(FAMILY)
    if not vals:
        return None
    out = {}
    for labels, value in vals.items():
        d = dict(_LABEL.findall(labels))
        out[d.get("phase"), d.get("clock")] = value
    return out


def between(a, b):
    """What the clock and the step count advanced from scrape ``a`` to
    scrape ``b``: ``({(phase, clock): seconds}, steps)``."""
    sa, sb = _seconds(a), _seconds(b)
    if sa is None or sb is None or STEPS not in a or STEPS not in b:
        return None
    steps = sum(b[STEPS].values()) - sum(a[STEPS].values())
    return {k: v - sa.get(k, 0.0) for k, v in sb.items()}, steps


def window(src):
    """``between`` of the window's first and last scrape."""
    md = src.get("metrics_delta")
    if not md:
        return None
    return between(md["start"], md["end"])


def busy_s(delta, clock="wall"):
    """Seconds on ``clock`` in every phase but the two the host waits in:
    the host's own work."""
    return sum(v for (phase, c), v in delta.items()
               if c == clock and phase not in WAITING)


def ms_per_step(src, phase):
    """Wall milliseconds a step of the window spent in ``phase``."""
    w = window(src)
    if not w or not w[1] or (phase, "wall") not in w[0]:
        return None
    return 1e3 * w[0][phase, "wall"] / w[1]


def bracket(src):
    """The traced run's two consecutive scrapes that bracket the device
    trace (the one pair more than the trace's length apart: the driver
    scrapes every second, except while it traces), as ``(i, i + 1)`` into
    ``metrics_delta["scrapes"]``; None where no trace was taken."""
    md, traced_s = src.get("metrics_delta"), src.get("trace_window_s")
    if not md or not traced_s or len(md["scrapes"]) < 3:
        return None
    times = [t for t, _ in md["scrapes"]]
    i = max(range(len(times) - 1), key=lambda k: times[k + 1] - times[k])
    return (i, i + 1) if times[i + 1] - times[i] > traced_s else None


def busy_ms_per_step_inside_and_outside(src):
    """The host's own work per step between the two scrapes that bracket
    the device trace, and over the rest of the window."""
    pair, whole = bracket(src), window(src)
    if pair is None or not whole:
        return None
    scrapes = src["metrics_delta"]["scrapes"]
    inside = between(scrapes[pair[0]][1], scrapes[pair[1]][1])
    if not inside or not inside[1] or whole[1] <= inside[1]:
        return None
    outside_s = busy_s(whole[0]) - busy_s(inside[0])
    return (1e3 * busy_s(inside[0]) / inside[1],
            1e3 * outside_s / (whole[1] - inside[1]))
