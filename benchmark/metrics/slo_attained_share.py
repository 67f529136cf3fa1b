"""Gateway: share of attempted requests that met the mix's limits on the
client's clock (TTFT and every gap, ``slo`` in the mix file); a failed
request misses."""
import window


def reduce(src):
    slo = src.get("mix", {}).get("slo")
    if "client" not in src or not slo:
        return None
    return window.slo_attained_share(src["client"], src["window"],
                                     slo["ttft_ms"], slo["gap_ms"])
