"""Kernels: the least time the chip could take for the two attention layers'
kernel calls of exactly the traced steps (``attn_pairs`` causal pairs at 20
query heads of 128, ``kv_tokens`` cached rows of ONE KV head read once
whatever the number of query heads that share them, the queries read and the
outputs written: ``flops_bytes_jamba.attention_work``), over the device time
of the ragged kernel's calls under the ``jamba_attn`` scope. A chunk's 512
queries over a prefix of thousands of keys are bound by the MXU, a decode
row by the memory; the bound is taken over the traced steps' sums."""
import flops_bytes
import flops_bytes_jamba
import jamba_trace


def reduce(src):
    secs = jamba_trace.of(src)
    if not secs or not secs["jamba_attn/ragged"] or "peaks" not in src:
        return None
    args = jamba_trace.traced_dispatch_args(src)
    if not args:
        return None
    flops, nbytes = flops_bytes_jamba.attention_work(
        src["model"], sum(a["attn_pairs"] for a in args),
        sum(a["kv_tokens"] for a in args),
        sum(a["decode_tokens"] + a["prefill_tokens"] for a in args))
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["jamba_attn/ragged"]
