"""Kernels: the share of the ragged kernel's grid steps that pass its
``pl.when`` and compute, summed over the window's ``dispatch`` spans
(``live_steps`` / ``grid_steps``, counted by the engine from each step's
``qstart / qlen / kvlen`` with ``ragged_grid_counts``)."""
import timeline


def reduce(src):
    args = timeline.dispatch_args(src)
    if not args:
        return None
    grid = sum(a["grid_steps"] for a in args)
    live = sum(a["live_steps"] for a in args)
    return 100.0 * live / grid if grid else None
