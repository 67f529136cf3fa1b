"""Median gap between a request's consecutive streamed tokens, pooled over
requests, for gaps that end inside the window: the time per output token a
client sees. (The 95th percentile sits where steps that carry a whole-prompt
prefill begin, about one gap in twenty, and so flips between two modes from
seed to seed; it is kept as a per-layer reading.)"""
import window


def reduce(src):
    if "client" not in src:
        return None
    return window.percentile(window.gaps_ms(src["client"], src["window"]), 50)
