"""Step programs: the share of its time the driver thread spends waiting for
the chip: wall seconds of ``device-wait`` over those of every phase but
``idle-wait`` (``serving_driver_seconds_total``). Near 0 the host is the
pace: a millisecond taken off the device's step will not show."""
import driver_clock


def reduce(src):
    w = driver_clock.window(src)
    if not w:
        return None
    wall = {p: v for (p, c), v in w[0].items() if c == "wall"}
    working = sum(v for p, v in wall.items() if p != "idle-wait")
    if "device-wait" not in wall or working <= 0:
        return None
    return 100.0 * wall["device-wait"] / working
