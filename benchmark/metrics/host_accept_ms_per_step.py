"""Step programs: the ``host-accept`` span per step. The launch is
asynchronous, so this span holds the wait for the device's result as well
as the host's own work on it; read it against ``step_ms_mean``."""
import readers


def reduce(src):
    return readers.span_ms_per_step(src, "host-accept")
