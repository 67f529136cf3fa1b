"""Kernels: the least time the chip could take for the prefill chunks' delta
rule of exactly the traced steps, all nine linear layers, over the device time
of the kernel by its name (``gdn_chunk_scan``), on the ``scan_tokens`` /
``scan_spans`` of the ``dispatch`` spans of the steps inside the traced window
(``flops_bytes_qwen3_next.recurrence_work``): the larger of the operations
over the MXU's peak and the bytes over the memory's. Only the recurrence's own
``7 dk dv`` operations a token a value head count: the chunked form's Gram
matrices and triangular solve are overhead, and the decode-only program
launches no scan at all."""
import flops_bytes
import flops_bytes_qwen3_next
import qwen3_next_trace


def reduce(src):
    secs = qwen3_next_trace.of(src)
    if not secs or not secs["gdn_chunk_scan"] or "peaks" not in src:
        return None
    args = qwen3_next_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_qwen3_next.recurrence_work(
        src["model"], sum(a["scan_tokens"] for a in args),
        sum(a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["gdn_chunk_scan"]
