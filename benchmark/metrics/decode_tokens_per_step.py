"""Scheduler: decode tokens (decode rows times their fused ticks) per step,
delta ``serving_step_tokens_total{kind="decode"}`` over delta steps."""
import timeline


def reduce(src):
    return timeline.step_tokens_per_step(src, "decode")
