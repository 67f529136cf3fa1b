"""Kernels: the least time the chip could take for the prefill chunks'
selective scan of exactly the traced steps, all 26 Mamba layers, over the
device time of the kernel by its name (``ssm_chunk_scan``), on the
``scan_tokens`` / ``scan_spans`` of the ``dispatch`` spans of the steps inside
the traced window (``flops_bytes_jamba.recurrence_work``). The bound divides
VECTOR work by the MXU's peak (``flops_bytes.least_seconds``; ``peaks.json``
states none for the vector units), so by that rule the memory bounds the scan
(a token moves 61,568 B a layer, 75 ns at 819 GB/s; its 573,440 operations at
197 TFLOP/s would be 3 ns): the reading is a LOWER bound of the share of the
true peak, and says how far the scan is from being free, not how well the
vector units are used."""
import flops_bytes
import flops_bytes_jamba
import jamba_trace
import ssm_trace


def reduce(src):
    secs = ssm_trace.of(src)
    if not secs or not secs["ssm_chunk_scan"] or "peaks" not in src:
        return None
    args = jamba_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_jamba.recurrence_work(
        src["model"], sum(a["scan_tokens"] for a in args),
        sum(a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["ssm_chunk_scan"]
