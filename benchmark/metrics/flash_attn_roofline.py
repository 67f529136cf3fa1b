"""Kernels: least time for the flash-attention calls of the trace (forward,
dK/dV and dQ kernels, told apart by their shapes) over their device time.
Calls whose signature is none of the three are left out of both sides."""
import flops_bytes


def reduce(src):
    x = src.get("xplane")
    if not x or "peaks" not in src:
        return None
    least = spent = 0.0
    for sig, rec in x["mosaic_calls"].items():
        kind = flops_bytes.classify_flash(sig)
        if kind is None:
            continue
        flops, nbytes = flops_bytes.flash_call(*kind)
        least += rec["count"] * flops_bytes.least_seconds(
            flops, nbytes, src["peaks"])[0]
        spent += rec["seconds"]
    return 100.0 * least / spent if spent else None
