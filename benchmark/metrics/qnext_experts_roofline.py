"""Kernels: the least time the chip could take for the three grouped matmuls
over the HELD experts of exactly the traced steps, all twelve layers, over
their device time (the ops under the ``moe_experts`` scope): the bytes of the
experts TOUCHED (the engine's counter, summed over the layer calls of the
traced steps) plus the live pairs' rows, the pairs' multiply-adds
(``flops_bytes_qwen3_next.experts_work``). At 2.5 pairs a held expert a decode
step is bound by the weight stream."""
import flops_bytes
import flops_bytes_qwen3_next
import qwen3_next_trace


def reduce(src):
    secs = qwen3_next_trace.of(src)
    counts = qwen3_next_trace.traced_moe_counts(src)
    if not secs or not secs["moe_experts"] or not counts \
            or "peaks" not in src:
        return None
    flops, nbytes = flops_bytes_qwen3_next.experts_work(
        src["model"], counts["moe_pairs"], counts["moe_experts_touched"])
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["moe_experts"]
