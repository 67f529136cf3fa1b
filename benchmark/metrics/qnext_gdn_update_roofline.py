"""Kernels: the least time the chip could take for the decode rows' delta-rule
update of exactly the traced steps, all nine linear layers at 32 value heads
on 16 key heads, over the device time of the kernel by its name
(``gdn_recurrent_update``): a live row's float32 state ``[128, 32 x 128]`` read
and written a layer, its ``q`` and ``k`` ONCE at 16 heads, its ``v``, ``o`` and
gates (``flops_bytes_qwen3_next.update_work`` on the spans of one token:
``state_rows - scan_spans`` of the ``dispatch`` spans). Bound by the memory;
the operations are the vector units' and are divided by the MXU's peak, so the
reading is a LOWER bound of the share of the true peak."""
import flops_bytes
import flops_bytes_qwen3_next
import qwen3_next_trace


def reduce(src):
    secs = qwen3_next_trace.of(src)
    if not secs or not secs["gdn_recurrent_update"] or "peaks" not in src:
        return None
    args = qwen3_next_trace.traced_dispatch_args(src)
    if not args:
        return None
    ops, nbytes = flops_bytes_qwen3_next.update_work(
        src["model"], sum(a["state_rows"] - a["scan_spans"] for a in args))
    least, _bound = flops_bytes.least_seconds(ops, nbytes, src["peaks"])
    return 100.0 * least / secs["gdn_recurrent_update"]
