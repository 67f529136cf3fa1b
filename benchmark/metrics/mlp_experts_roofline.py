"""Kernels: the least time the chip could take for the grouped matmuls over
the held TWO-MATRIX experts of exactly the traced steps, over their device
time (the ops under the ``moe_experts`` scope). As
``moe_held_experts_roofline``, but an expert is ``relu(x W_up)^2 W_down``: two
matrices at ``moe_intermediate_size`` (``flops_bytes_ssd.mlp_experts_work``),
where ``flops_bytes_mla.held_experts_work`` reckons a SwiGLU's three."""
import flops_bytes
import flops_bytes_ssd
import moe_trace
import ssd_trace


def reduce(src):
    secs, counts = moe_trace.of(src), moe_trace.counted(src)
    if not ssd_trace.of(src) or not secs or not secs["moe_experts"] \
            or not counts or "peaks" not in src:
        return None
    flops, nbytes = flops_bytes_ssd.mlp_experts_work(
        src["model"], counts["moe_pairs"], counts["moe_experts_touched"])
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["moe_experts"]
