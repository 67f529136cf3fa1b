"""Kernels: the least time the chip could take for the attention over the
SELECTED rows of exactly the traced steps (``flops_bytes_dsa.attention_work``:
``min(position + 1, index_topk)`` latent rows a (query, layer), the absorbed
form's FLOPs a selected triple) over the device time under the ``dsa_attend``
scope. The same work whatever implements it: a kernel that walks every cached
row and masks reads low here by construction, by about
``dsa_rows_read_over_selected``."""
import dsa_trace
import flops_bytes
import flops_bytes_dsa


def reduce(src):
    secs, n = dsa_trace.of(src), dsa_trace.traced_counts(src)
    if not secs or not secs["dsa_attend"] or not n or "peaks" not in src \
            or "index_topk" not in src.get("model", {}):
        return None
    flops, nbytes = flops_bytes_dsa.attention_work(
        src["model"], n["selected_rows"],
        n["query_tokens"] * src["model"]["num_hidden_layers"])
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["dsa_attend"]
