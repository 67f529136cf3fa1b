"""Trainer: the share of the window that steps took beyond the median step
(the sum of interval - median over the intervals longer than it, over the
window's length): what ``train_tokens_per_s``, a median, leaves out. A
stall of the host, a slow fetch or a save shows here. Read in the traced
run, so it holds the profiler's start and stop as well."""
import readers
import window


def reduce(src):
    gaps = readers.step_intervals_s(src)
    if len(gaps) < 2:
        return None
    med = window.percentile(gaps, 50)
    return 100.0 * sum(g - med for g in gaps if g > med) \
        / src["child"]["window_s"]
