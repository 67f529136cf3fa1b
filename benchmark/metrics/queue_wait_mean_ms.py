"""Gateway: mean wait from arrival to a slot, over requests admitted in the
window (delta of ``serving_queue_wait_seconds`` sum over count)."""
import readers


def reduce(src):
    return readers.ratio_ms(src, "serving_queue_wait_seconds")
