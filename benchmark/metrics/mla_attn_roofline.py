"""Kernels: the least time the chip could take for the absorbed-form latent
attention of exactly the traced steps, over the device time of the kernel by
its name (``mla_ragged_attention``; never all Mosaic time, which holds the
grouped matmuls too). The required work is counted: ``attn_pairs``,
``kv_tokens`` and the live tokens of the ``dispatch`` spans of the steps
inside the traced window, matched by step number; ``flops_bytes_mla`` turns
them into FLOPs and bytes."""
import flops_bytes
import flops_bytes_mla
import mla_trace
import timeline


def reduce(src):
    secs, tl = mla_trace.of(src), timeline.of(src)
    if not secs or not secs["kernel"] or not tl or not tl["steps"] \
            or "peaks" not in src:
        return None
    args = timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]})
    if not args:
        return None
    flops, nbytes = flops_bytes_mla.absorbed_attention_work(
        src["model"], sum(a["attn_pairs"] for a in args),
        sum(a["kv_tokens"] for a in args),
        sum(a.get("decode_tokens", 0) + a.get("prefill_tokens", 0)
            for a in args))
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["kernel"]
