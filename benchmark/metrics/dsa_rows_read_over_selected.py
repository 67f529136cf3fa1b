"""Kernels: pool rows the attention scored over rows its queries selected,
summed over the window's ``dispatch`` spans (``attended_rows`` /
``selected_rows``): 1 for a kernel that gathers the selection, about context
/ ``index_topk`` for one that walks the whole diagonal and masks. Says which
way the cell runs, and what a later change of way has to win."""
import dsa_trace


def reduce(src):
    n = dsa_trace.window_counts(src)
    if not n or not n["selected_rows"]:
        return None
    return n["attended_rows"] / n["selected_rows"]
