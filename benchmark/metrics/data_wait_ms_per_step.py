"""Trainer: host time in the loader's ``next`` per step: the child's own
``data_fetch`` annotation in the trace, or its clock where no trace ran."""


def reduce(src):
    x, c = src.get("xplane"), src.get("child", {})
    ann = (x or {}).get("annotations", {}).get("data_fetch")
    if ann and ann["count"]:
        return 1e3 * ann["seconds"] / ann["count"]
    return c.get("data_fetch_ms_mean")
