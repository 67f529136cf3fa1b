"""Kernels: device time of the ops named ``ragged_paged_attention`` (the full
layers' attention) over device busy time, in the traced part of the window.
``attn_kernel_share`` divides ALL Mosaic time, which in a hybrid model holds
the two delta-rule kernels too."""
import gdn_trace


def reduce(src):
    return gdn_trace.share_of_busy(src, "ragged_paged_attention")
