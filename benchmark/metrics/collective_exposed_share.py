"""Mesh / collectives: time a collective was in flight with no compute op
running on that chip, over the traced window, mean over the chips."""


def reduce(src):
    x = src.get("xplane")
    if not x or not x["window_s"] or x["devices"] < 2:
        return None
    return 100.0 * x["collective_exposed_s"] / x["window_s"]
