"""Kernels: the least time the chip could take for the prefill chunks'
Mamba-2 scan of exactly the traced steps, over the device time of the kernel
by its name (``ssd_chunk_scan``), on the ``ssd_scan_tokens`` /
``ssd_scan_spans`` of the ``dispatch`` spans of the steps inside the traced
window (``flops_bytes_ssd.scan_work``: the dual form's matrix products, a
token's rows, a span's state read and written once). Steps without a chunk run
the decode-only program, which has no such kernel: they add nothing to either
side."""
import flops_bytes
import flops_bytes_ssd
import ssd_trace


def reduce(src):
    secs = ssd_trace.of(src)
    if not secs or not secs["ssd_chunk_scan"] or "peaks" not in src:
        return None
    args = ssd_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_ssd.scan_work(
        src["model"], sum(a["ssd_scan_tokens"] for a in args),
        sum(a["ssd_scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["ssd_chunk_scan"]
