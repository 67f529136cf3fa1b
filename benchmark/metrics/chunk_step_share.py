"""Scheduler: the share of the window's steps whose program carried a prefill
chunk, from the ``step`` spans' ``chunks``. A cell whose median gap is to be a
decode-only step needs this well under a half (a median over two kinds of
step is noise near it); lower leaves more steps to decode alone."""
import readers


def reduce(src):
    spans = readers.window_spans(src)
    if not spans:
        return None
    chunks = [e["args"]["chunks"] for e in spans
              if e.get("name") == "step" and "chunks" in e.get("args", {})]
    if not chunks:
        return None
    return 100.0 * sum(1 for c in chunks if c > 0) / len(chunks)
