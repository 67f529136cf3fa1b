"""Kernels: the least time the chip could take for the decode rows' state
update of exactly the traced steps, all 26 Mamba layers, over the device time
of the kernel by its name (``ssm_recurrent_update``): a row reads and writes
its float32 state ``[16, 5120]`` a layer (``flops_bytes_jamba.update_work`` on
the spans of one token: ``state_rows - scan_spans`` of the ``dispatch``
spans). Bound by the memory; as ``jamba_scan_roofline``, the operations are
the vector units' and are divided by the MXU's peak, so the reading is a
LOWER bound of the share of the true peak. A step with no decode row costs
the kernel a launch and no work: its time stays in the denominator."""
import flops_bytes
import flops_bytes_jamba
import jamba_trace
import ssm_trace


def reduce(src):
    secs = ssm_trace.of(src)
    if not secs or not secs["ssm_recurrent_update"] or "peaks" not in src:
        return None
    args = jamba_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_jamba.update_work(
        src["model"], sum(a["state_rows"] - a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["ssm_recurrent_update"]
