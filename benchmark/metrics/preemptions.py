"""KV cache: sequences preempted in the window."""
import readers


def reduce(src):
    return readers.delta(src, "serving_preemptions_total")
