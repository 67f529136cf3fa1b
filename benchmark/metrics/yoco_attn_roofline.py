"""Kernels: the least time the chip could take to read the one cache for the
layers that read it in the traced steps (``kv_tokens`` of the ``dispatch``
spans: every live row's whole cache, a layer call, times the middle full
layer and the cross layers; ``flops_bytes_ssm.cache_bytes``), over the device
time of the ragged kernel's calls under the ``yoco_attn`` scope. Bound by the
memory in a decode-only step (one query token a row); a chunk step's middle
layer call also multiplies a chunk's 512 queries by its keys, which is counted
as bytes only, so a trace with many chunk steps reads lower."""
import flops_bytes
import flops_bytes_ssm
import ssm_trace


def reduce(src):
    secs = ssm_trace.of(src)
    if not secs or not secs["yoco_attn/ragged"] or "peaks" not in src:
        return None
    args = ssm_trace.traced_dispatch_args(src)
    if not args:
        return None
    nbytes = flops_bytes_ssm.cache_bytes(
        src["model"], sum(a["kv_tokens"] for a in args))
    least, _bound = flops_bytes.least_seconds(0, nbytes, src["peaks"])
    return 100.0 * least / secs["yoco_attn/ragged"]
