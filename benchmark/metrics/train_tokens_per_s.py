"""Tokens of one step, all chips together, over the median interval between
the arrivals of consecutive steps' losses inside the window (child clock):
the rate the trainer holds step after step.

Why the median and not the window's tokens over its length: the device's
step repeats to 0.01 % (PERF.md section 2), yet in the driver's first check
of PR 23 the quotient over the whole window spread 0.001 % in one set of six
runs on one chip and 1.5 % in the other. A whole run that is slower by a
few steps' time, on a machine whose host is shared, reads as stalls (not
seen directly: the driver's logs are not kept); the quotient carries a
stall in full and a bound of 1 % cannot hold it. What the median leaves
out, ``train_stall_share`` reports."""
import readers
import window


def reduce(src):
    gaps = readers.step_intervals_s(src)
    if len(gaps) < 2:
        return None
    return src["child"]["tokens_per_step"] / window.percentile(gaps, 50)
