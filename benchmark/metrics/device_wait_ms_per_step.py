"""Step programs: the ``device-wait`` span per step, the fence on the step's
result (``np.asarray(toks)``). ``step_ms_mean`` less this is the host's
serial share of a step, with no profiler at all."""
import readers


def reduce(src):
    spans = readers.window_spans(src) or ()
    waits = [e["dur"] for e in spans if e.get("name") == "device-wait"]
    steps = sum(1 for e in spans if e.get("name") == "step")
    if not waits or not steps:
        return None         # a program that does not split ``launch``
    return sum(waits) / 1e3 / steps
