"""KV cache: rows the queries selected over the rows a dense walk of the same
caches would read (every layer's causal (query, key) pairs), summed over the
window's ``dispatch`` spans, in percent: how hard the selection binds under
this mix (100 where every context is at most ``index_topk``)."""
import dsa_trace


def reduce(src):
    n = dsa_trace.window_counts(src)
    layers = src.get("model", {}).get("num_hidden_layers")
    if not n or not n["attn_pairs"] or not layers:
        return None
    return 100.0 * n["selected_rows"] / (layers * n["attn_pairs"])
