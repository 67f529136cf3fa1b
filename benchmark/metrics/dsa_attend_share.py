"""Kernels: device time of the ops under the ``dsa_attend`` scope (the
absorbed-form attention over the selected rows of the latent pool, every
layer) over device busy time, in the traced part of the window."""
import dsa_trace


def reduce(src):
    return dsa_trace.share_of_busy(src, "dsa_attend")
