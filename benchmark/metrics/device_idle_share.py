"""Device: 1 - busy union / traced window, mean over the chips."""


def reduce(src):
    x = src.get("xplane")
    return None if not x else 100.0 * x["idle_share"]
