"""Kernels: the least time the chip could take for the five window layers'
attention of exactly the traced steps (``window_attn_pairs`` pairs inside the
window at 64 query heads of 192 + 128, ``window_kv_tokens`` cached rows,
``min(context, 128)`` a decode row, of 8 KV heads at 5,120 B a token a layer,
the queries read and the outputs written: ``flops_bytes_mimo.attention_work``),
over the device time of the ragged kernel's calls under the ``window_attn``
scope (its calls over the rings). The walk fetches whole groups of blocks from
the group the window starts in (``window_fetched_keys`` of the ``dispatch``
spans against ``window_kv_tokens``), which is in the time and not in the
bytes: a 128-key walk reads low here, and that is the reading."""
import flops_bytes
import flops_bytes_mimo
import mimo_trace


def reduce(src):
    secs = mimo_trace.of(src)
    if not secs or not secs["window_attn/ragged"] or "peaks" not in src:
        return None
    args = mimo_trace.traced_dispatch_args(src)
    if not args:
        return None
    flops, nbytes = flops_bytes_mimo.attention_work(
        src["model"], sum(a["window_attn_pairs"] for a in args),
        sum(a["window_kv_tokens"] for a in args),
        sum(a["decode_tokens"] + a["prefill_tokens"] for a in args),
        window=True)
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["window_attn/ragged"]
