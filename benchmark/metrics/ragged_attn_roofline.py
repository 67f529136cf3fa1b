"""Kernels: the least time the chip could take for the attention work of
the window, over the Mosaic kernels' device time.

The ragged kernel's work depends on run-time lengths, which its shapes do
not show, so the required work is counted from the client's record: every
token decoded in the window attends to its request's context at that
moment, and every prompt whose first token fell in the window was prefilled
(P (P + 1) / 2 pairs). ``flops_bytes.ragged_work`` turns that into FLOPs
and bytes; the larger of FLOPs / peak and bytes / peak is the least time,
for the window, scaled to the traced part (steady state assumed: in an
open loop below its knee the traced part is laid on a request,
``trafficgen.trace_start_s``, and is busier than the window's mean, so the
share reads low there; ``ragged_attn_roofline_counted`` assumes nothing)."""
import flops_bytes
import readers
import window


def work(src):
    win, decoded, prefilled = src["window"], [], []
    for r in src["client"]:
        for k, t in enumerate(r["token_times"]):
            if not window.in_window(t, win):
                continue
            if k == 0:
                prefilled.append(r["prompt_len"])
            else:
                decoded.append(r["prompt_len"] + k)
    return flops_bytes.ragged_work(src["model"], decoded, prefilled)


def reduce(src):
    x = src.get("xplane")
    if not x or not x["mosaic_s"] or "client" not in src \
            or "peaks" not in src:
        return None
    flops, nbytes = work(src)
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * readers.scaled_to_trace(src, least) / x["mosaic_s"]
