"""Device: 1 - busy union / traced window, mean over the chips.

The traced window runs from the first to the last device event of the
traced seconds, which ``trafficgen.trace_start_s`` places: the middle of the
window in a closed loop; in an open loop a request of the schedule, so the
reading is the idle of serving steps, plus the empty server between two
requests where a second one arrives inside the trace, and never the chance
of the middle holding no request at all."""


def reduce(src):
    x = src.get("xplane")
    return None if not x else 100.0 * x["idle_share"]
