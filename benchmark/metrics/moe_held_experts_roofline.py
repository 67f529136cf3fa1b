"""Kernels: the least time the chip could take for the grouped matmuls over
the HELD experts of exactly the traced steps, over their device time (the ops
under the ``moe_experts`` scope). As ``moe_experts_roofline``, but one expert
is ``moe_intermediate_size`` wide (``flops_bytes_mla.held_experts_work``):
the configuration's ``intermediate_size`` is its dense layers' width."""
import flops_bytes
import flops_bytes_mla
import moe_trace


def reduce(src):
    secs, counts = moe_trace.of(src), moe_trace.counted(src)
    if not secs or not secs["moe_experts"] or not counts \
            or "peaks" not in src \
            or "moe_intermediate_size" not in src.get("model", {}):
        return None
    flops, nbytes = flops_bytes_mla.held_experts_work(
        src["model"], counts["moe_pairs"], counts["moe_experts_touched"])
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["moe_experts"]
