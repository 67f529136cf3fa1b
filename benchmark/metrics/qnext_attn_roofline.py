"""Kernels: the least time the chip could take for the three full layers'
attention of exactly the traced steps (``attn_pairs`` causal pairs at 16 query
heads of 256, ``kv_tokens`` cached rows of 2 KV heads read once whatever the
number of query heads that share them, the queries read and the outputs
written: ``flops_bytes_qwen3_next.attention_work``), over the device time of
the ragged kernel by its name (``ragged_paged_attention``). A decode row is
bound by the memory, a chunk's 512 queries by the MXU; the bound is taken over
the traced steps' sums."""
import flops_bytes
import flops_bytes_qwen3_next
import qwen3_next_trace


def reduce(src):
    secs = qwen3_next_trace.of(src)
    if not secs or not secs["ragged_paged_attention"] or "peaks" not in src:
        return None
    args = qwen3_next_trace.traced_dispatch_args(src)
    if not args:
        return None
    flops, nbytes = flops_bytes_qwen3_next.attention_work(
        src["model"], sum(a["attn_pairs"] for a in args),
        sum(a["kv_tokens"] for a in args),
        sum(a["decode_tokens"] + a["prefill_tokens"] for a in args))
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["ragged_paged_attention"]
