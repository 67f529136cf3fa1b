"""Kernels: the least time the chip could take for the decode rows' delta-rule
update of exactly the traced steps, over the device time of the kernel by its
name (``gdn_recurrent_update``). The required work is counted: the spans of
one token (``state_rows - scan_spans``) of the ``dispatch`` spans of the steps
inside the traced window; ``flops_bytes_gdn`` turns them into FLOPs and
bytes."""
import flops_bytes
import flops_bytes_gdn
import gdn_trace


def reduce(src):
    secs = gdn_trace.of(src)
    if not secs or not secs["gdn_recurrent_update"] or "peaks" not in src:
        return None
    args = gdn_trace.traced_dispatch_args(src)
    if not args:
        return None
    flops, nbytes = flops_bytes_gdn.update_work(
        src["model"], sum(a["state_rows"] - a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["gdn_recurrent_update"]
