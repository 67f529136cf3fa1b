"""Kernels: the least time the chip could take to read the keys and values
the window layers' queries may see in the traced steps (``window_kv_tokens``
of the ``dispatch`` spans, a layer call: ``min(kv_len, span + window - 1)``
rows a span, times the window layers; ``flops_bytes_ssm.window_bytes``), over
the device time of the ragged kernel's calls under the ``window_attn`` scope.
Bound by the memory: a decode row reads 512 keys and values for its 40
queries. What the walk fetches beyond the window (whole blocks of a whole
group) is in the time and not in the bytes."""
import flops_bytes
import flops_bytes_ssm
import ssm_trace


def reduce(src):
    secs = ssm_trace.of(src)
    if not secs or not secs["window_attn/ragged"] or "peaks" not in src:
        return None
    args = ssm_trace.traced_dispatch_args(src)
    if not args or not all("window_kv_tokens" in a for a in args):
        return None
    nbytes = flops_bytes_ssm.window_bytes(
        src["model"], sum(a["window_kv_tokens"] for a in args))
    least, _bound = flops_bytes.least_seconds(0, nbytes, src["peaks"])
    return 100.0 * least / secs["window_attn/ragged"]
