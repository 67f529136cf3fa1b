"""Kernels: the least time the chip could take for the decode rows' state
updates of exactly the traced steps, over the device time of the kernel by
its name (``ssm_recurrent_update``). The required work is counted: the spans
of one token (``state_rows - scan_spans``) of the ``dispatch`` spans of the
steps inside the traced window; ``flops_bytes_ssm.update_work`` turns them
into operations and bytes (the state read and written, the row's inputs). A
decode row is bound by the memory: 0.66 MB of state a row a layer."""
import flops_bytes
import flops_bytes_ssm
import ssm_trace


def reduce(src):
    secs = ssm_trace.of(src)
    if not secs or not secs["ssm_recurrent_update"] or "peaks" not in src:
        return None
    args = ssm_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_ssm.update_work(
        src["model"], sum(a["state_rows"] - a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["ssm_recurrent_update"]
