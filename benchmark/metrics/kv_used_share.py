"""KV cache: mean of ``kv_block_table_fill`` over the one-second scrapes of
the window: the share of the block tables that live sequences fill."""


def reduce(src):
    md = src.get("metrics_delta")
    if not md:
        return None
    vals = [sum(s["kv_block_table_fill"].values())
            for _, s in md["scrapes"] if "kv_block_table_fill" in s]
    return 100.0 * sum(vals) / len(vals) if vals else None
