"""Kernels: as ``ragged_attn_roofline``, with the required work counted and
not inferred: ``kv_tokens`` and ``attn_pairs`` of the ``dispatch`` spans of
exactly the steps inside the traced window (matched by step number to the
``step`` annotations of the trace), so no steady window is assumed. Per layer
call the kernel must read ``kv_tokens`` K and V rows and multiply
``attn_pairs`` query-key pairs (4 FLOPs a pair and lane); the denominator is
the device time of the Mosaic calls in the trace."""
import flops_bytes
import timeline


def reduce(src):
    x, tl = src.get("xplane"), timeline.of(src)
    if not x or not x["mosaic_s"] or not tl or not tl["steps"] \
            or "peaks" not in src:
        return None
    args = timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]})
    if not args:
        return None
    # the yardstick's own constants: ``ragged_work`` charges one decoded
    # token of context n with n pairs and n cache rows, over all layers
    pairs = sum(a["attn_pairs"] for a in args)
    rows = sum(a["kv_tokens"] for a in args)
    flops = flops_bytes.ragged_work(src["model"], [pairs], [])[0]
    nbytes = flops_bytes.ragged_work(src["model"], [rows], [])[1]
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / x["mosaic_s"]
