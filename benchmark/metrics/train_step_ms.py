"""Trainer: median of ten steps, each timed to its own
``block_until_ready`` on the child's clock, after the window."""
import window


def reduce(src):
    return window.percentile(src.get("child", {}).get("blocked_step_ms"), 50)
