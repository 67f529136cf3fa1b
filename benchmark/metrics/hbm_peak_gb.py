"""Device: ``memory_stats()["peak_bytes_in_use"]`` on the fullest chip."""


def reduce(src):
    peak = src["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
