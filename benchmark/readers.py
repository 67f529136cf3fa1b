"""Helpers the metric readers under ``metrics/`` share: deltas of
Prometheus families over the window, and the host spans that fall in it.

``src`` is what a kind's ``drive()`` returns (see README.md): ``client``
(request records), ``window`` (start, end on the client clock),
``metrics_delta`` (``start``, ``end``, ``scrapes``), ``span_export``,
``debug_profile``, ``xplane`` (the summary of ``xplane_reduce``), ``child``
(the child's own report), plus ``config``, ``mix``, ``model``, ``peaks``,
``seconds``, ``setup_s``. A reader returns None when its source is absent.
"""
import importlib.util
import os


def same_as(name):
    """The ``reduce`` of the metric file ``metrics/<name>.py``, for a
    metric that is the same reading under another name: an entry of
    ``BENCHMARK.json`` has one ``moves``, so a reading taken in serving and
    in training cells is two entries, and one body."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def delta(src, family):
    """End minus start of a counter family, summed over its label sets."""
    md = src.get("metrics_delta")
    if not md:
        return None

    def total(scrape):
        vals = scrape.get(family)
        if vals is None:
            return None
        return sum(vals.values())

    a, b = total(md["start"]), total(md["end"])
    if a is None or b is None:
        return None
    return b - a


def ratio_ms(src, family):
    """Mean of a histogram over the window, in ms: delta sum / delta count."""
    s, n = delta(src, family + "_sum"), delta(src, family + "_count")
    if not n:
        return None
    return 1e3 * s / n


def steps_in_window(src):
    n = delta(src, "serving_step_duration_seconds_count")
    return int(n) if n else None


def window_spans(src):
    """The exported host spans of the window: from the first of the last
    ``steps_in_window`` ``step`` spans on. (The tracer's clock has its own
    origin, so the window is found by counting steps back from its end.)"""
    doc, n = src.get("span_export"), steps_in_window(src)
    if not doc or not n:
        return None
    steps = [e for e in doc["traceEvents"]
             if e.get("name") == "step" and e.get("ph") == "X"]
    if not steps:
        return None
    t_lo = steps[-min(n, len(steps))]["ts"]
    return [e for e in doc["traceEvents"] if e.get("ts", -1) >= t_lo]


def span_ms_per_step(src, name):
    spans = window_spans(src)
    if not spans:
        return None
    steps = sum(1 for e in spans if e.get("name") == "step")
    total_us = sum(e.get("dur", 0.0) for e in spans if e.get("name") == name)
    return total_us / 1e3 / steps if steps else None


def step_intervals_s(src):
    """Training: seconds between the arrivals of consecutive steps' losses
    inside the window, on the child's clock."""
    done = src.get("child", {}).get("step_done_s") or []
    return [b - a for a, b in zip(done, done[1:])]


def scaled_to_trace(src, seconds_of_window_work):
    """Work counted over the whole window, scaled to the traced part of it
    (steady state assumed)."""
    x = src.get("xplane")
    if not x:
        return None
    return seconds_of_window_work * x["window_s"] / src["seconds"]
