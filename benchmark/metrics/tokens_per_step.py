"""Scheduler: tokens packed into the unified step program per step (decode
rows plus prefill-chunk tokens), mean of the ``step`` spans' ``tokens``."""
import readers


def reduce(src):
    spans = readers.window_spans(src)
    if not spans:
        return None
    toks = [e["args"]["tokens"] for e in spans
            if e.get("name") == "step" and "tokens" in e.get("args", {})]
    return sum(toks) / len(toks) if toks else None
