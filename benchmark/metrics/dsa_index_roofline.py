"""Kernels: the least time the chip could take for the index scores of
exactly the traced steps (``flops_bytes_dsa.index_work``: every cached index
key of a row read once a layer that has an indexer, a dot of
``index_head_dim`` a (query, key, head); decode rows are bound by the keys'
bytes, a 512-token chunk by the FLOPs) over the device time under the
``dsa_index_score`` scope. The work is counted on the ``dispatch`` spans of
the traced steps, matched by step number."""
import dsa_trace
import flops_bytes
import flops_bytes_dsa


def reduce(src):
    secs, n = dsa_trace.of(src), dsa_trace.traced_counts(src)
    if not secs or not secs["dsa_index_score"] or not n \
            or "peaks" not in src \
            or "index_head_dim" not in src.get("model", {}):
        return None
    flops, nbytes = flops_bytes_dsa.index_work(
        src["model"], n["index_query_rows"], n["index_key_rows"],
        n["kv_tokens"])
    least, _bound = flops_bytes.least_seconds(flops, nbytes, src["peaks"])
    return 100.0 * least / secs["dsa_index_score"]
