"""Kernels: device time of the Mosaic (Pallas) custom calls over device
busy time, in the traced part of the window. In the serving step every
Mosaic call is the ragged paged-attention kernel."""


def reduce(src):
    x = src.get("xplane")
    if not x or not x["busy_s"]:
        return None
    return 100.0 * x["mosaic_s"] / x["busy_s"]
