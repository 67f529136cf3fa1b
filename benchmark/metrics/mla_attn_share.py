"""Kernels: device time of the ops under the ``mla_attend`` scope (the
absorbed-form attention kernel over the latent pool) over device busy time,
in the traced part of the window."""
import mla_trace


def reduce(src):
    return mla_trace.share_of_busy(src, "mla_attend")
