"""Scheduler: prefill-chunk tokens packed into the step, per step: delta
``serving_step_tokens_total{kind="prefill"}`` over delta steps."""
import timeline


def reduce(src):
    return timeline.step_tokens_per_step(src, "prefill")
