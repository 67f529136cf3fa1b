"""Kernels: the least time the chip could take for the prefill chunks'
selective scan of exactly the traced steps, over the device time of the
kernel by its name (``ssm_chunk_scan``), on the ``scan_tokens`` /
``scan_spans`` of the ``dispatch`` spans of the steps inside the traced
window. Which peak bounds it: by ``flops_bytes.least_seconds`` the MEMORY's
(a token moves 61,568 B, 75 ns at 819 GB/s; its 573,440 vector operations at
the MXU's 197 TFLOP/s would be 3 ns), but the operations are the VPU's and an
``exp`` a state element, which ``peaks.json`` has no peak for: the scan is in
truth bound by the vector units, so this share of the memory's roofline
reads low and says how far the scan is from being free, not how well the VPU
is used. Steps without a chunk cost the kernel a launch and no work: their
time stays in the denominator."""
import flops_bytes
import flops_bytes_ssm
import ssm_trace


def reduce(src):
    secs = ssm_trace.of(src)
    if not secs or not secs["ssm_chunk_scan"] or "peaks" not in src:
        return None
    args = ssm_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_ssm.recurrence_work(
        src["model"], sum(a["scan_tokens"] for a in args),
        sum(a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["ssm_chunk_scan"]
