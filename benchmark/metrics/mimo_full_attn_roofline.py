"""Kernels: the least time the chip could take for the two full layers'
attention of exactly the traced steps (``attn_pairs`` causal pairs at 64 query
heads of 192 + 128, ``kv_tokens`` cached rows of 4 KV heads at the MODEL's
2,560 B a token a layer, read once whatever the number of query heads that
share them, the queries read and the outputs written:
``flops_bytes_mimo.attention_work``), over the device time of the ragged
kernel's calls under the ``attn`` scope (its calls over the pool). A decode
row is bound by the memory, a chunk's 512 queries by the MXU; the bound is
taken over the traced steps' sums."""
import flops_bytes
import flops_bytes_mimo
import mimo_trace


def reduce(src):
    secs = mimo_trace.of(src)
    if not secs or not secs["attn/ragged"] or "peaks" not in src:
        return None
    args = mimo_trace.traced_dispatch_args(src)
    if not args:
        return None
    flops, nbytes = flops_bytes_mimo.attention_work(
        src["model"], sum(a["attn_pairs"] for a in args),
        sum(a["kv_tokens"] for a in args),
        sum(a["decode_tokens"] + a["prefill_tokens"] for a in args),
        window=False)
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["attn/ragged"]
