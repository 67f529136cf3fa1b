"""Scheduler: the rows that went through the cross-decoder (the layers after
the one that holds the shared cache) a step, mean over the traced steps, from
the ``dispatch`` spans' ``cross_rows``: ``num_slots`` where the packed buffer
narrows to one row a slot after the middle layers, the packed size (560 in a
step with a chunk) where it does not."""
import ssm_trace


def reduce(src):
    args = ssm_trace.traced_dispatch_args(src)
    if not args:
        return None
    return sum(a["cross_rows"] for a in args) / len(args)
