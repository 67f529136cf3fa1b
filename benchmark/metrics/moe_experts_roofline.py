"""Kernels: the least time the chip could take for the grouped matmuls of
exactly the traced steps, over their device time (the ops under the
``moe_experts`` scope). The required work is counted, not inferred: live
(token, expert) pairs and experts touched, per layer call, from the
``moe_*`` args of the traced steps' spans; ``flops_bytes_moe`` turns them
into FLOPs and bytes."""
import flops_bytes
import flops_bytes_moe
import moe_trace


def reduce(src):
    secs, counts = moe_trace.of(src), moe_trace.counted(src)
    if not secs or not secs["moe_experts"] or not counts \
            or "peaks" not in src:
        return None
    flops, nbytes = flops_bytes_moe.routed_ffn_work(
        src["model"], counts["moe_pairs"], counts["moe_experts_touched"])
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["moe_experts"]
