"""Kernels: the least time the chip could take for the decode rows' Mamba-2
state updates of exactly the traced steps, over the device time of the kernel
by its name (``ssd_recurrent_update``). The required work is counted:
``ssd_update_rows`` (rows of one token x Mamba-2 blocks) of the ``dispatch``
spans of the steps inside the traced window; ``flops_bytes_ssd.update_work``
turns them into operations and bytes (the state read and written, the row's
inputs). A decode row is bound by the memory: 2 x 2 MiB of state a row a
block."""
import flops_bytes
import flops_bytes_ssd
import ssd_trace


def reduce(src):
    secs = ssd_trace.of(src)
    if not secs or not secs["ssd_recurrent_update"] or "peaks" not in src:
        return None
    args = ssd_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_ssd.update_work(
        src["model"], sum(a["ssd_update_rows"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["ssd_recurrent_update"]
