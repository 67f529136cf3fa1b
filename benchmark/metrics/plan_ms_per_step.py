"""Scheduler: host time planning a step (``plan`` spans over steps)."""
import readers


def reduce(src):
    return readers.span_ms_per_step(src, "plan")
