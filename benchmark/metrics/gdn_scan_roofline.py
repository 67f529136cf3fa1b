"""Kernels: the least time the chip could take for the prefill chunks' delta
rule of exactly the traced steps, over the device time of the kernel by its
name (``gdn_chunk_scan``). The required work is the RECURRENCE's
(``flops_bytes_gdn``: the chunked form's extra products are overhead), on the
``scan_tokens`` / ``scan_spans`` of the ``dispatch`` spans of the steps inside
the traced window. Steps without a chunk cost the kernel a launch and no
work: their time stays in the denominator."""
import flops_bytes
import flops_bytes_gdn
import gdn_trace


def reduce(src):
    secs = gdn_trace.of(src)
    if not secs or not secs["gdn_chunk_scan"] or "peaks" not in src:
        return None
    args = gdn_trace.traced_dispatch_args(src)
    if not args:
        return None
    flops, nbytes = flops_bytes_gdn.recurrence_work(
        src["model"], sum(a["scan_tokens"] for a in args),
        sum(a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["gdn_chunk_scan"]
