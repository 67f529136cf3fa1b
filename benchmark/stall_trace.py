"""The host's long stalls, as the metric readers read them (PR 52).

What makes this possible is in the program: the driver clock has ten phases
(``sweep`` and ``retire`` were part of ``other``), counts every visit to a
phase longer than 8 ms (``serving_driver_long_visits_total``,
``serving_driver_long_visit_seconds_total{phase}``), the collector's pauses
are counted by generation (``serving_gc_pause_seconds_total``,
``serving_gc_collections_total{generation}``), and a traced run carries four
more spans on the device trace's clock: ``sweep`` and ``retire`` (the step's
first and last child), ``call`` (the jitted call inside ``dispatch``) and
``gc`` (a collection, on whichever thread it ran: it overlaps the engine's
spans and does not partition with them).

Two sources. The counters come from the window's first and last scrape, as
``driver_clock.py`` reads its family. The device's idle time under a span
comes from the trace ``timeline._find_trace`` finds, cut into gaps with
``xplane_reduce.gaps`` exactly as ``timeline.lay_out`` cuts it: both modules
are used as they are.

Every function returns None where the program lacks the family or the span
(a parent commit) and raises nothing; where it has them, a window in which
nothing of the kind happened reads 0.0.
"""
import json

import driver_clock
import readers
import timeline
import xplane_reduce

LONG_VISIT_S = "serving_driver_long_visit_seconds_total"
GC_PAUSE_S = "serving_gc_pause_seconds_total"
GC_COLLECTIONS = "serving_gc_collections_total"
#: a device gap this long is a stall: the shortest device step is 10.7 ms
LONG_GAP_S = 5e-3
#: the spans that name a gap: the step's leaves, the gateway's ``loop`` and
#: the collector
NAMED = ("admit", "plan", "sweep", "call", "dispatch", "device-wait",
         "host-accept", "retire", "loop", "gc")
#: the enclosing spans: they name no gap, and the list of long gaps gives
#: them beside the others, so that what is unnamed can be placed (under
#: ``launch`` and not ``dispatch``: a traced step's ``_dispatch_args``)
OUTER = ("launch", "step")
_NS = 1e-9      # the trace's own resolution


# ------------------------------------------------------------- the counters
def delta_by(src, family, label):
    """{label value: end minus start} of a counter family over the window;
    None where either scrape lacks it."""
    md = src.get("metrics_delta")
    if not md:
        return None
    a, b = md["start"].get(family), md["end"].get(family)
    if not a or not b:
        return None
    out = {}
    for labels, value in b.items():
        key = dict(driver_clock._LABEL.findall(labels)).get(label)
        out[key] = out.get(key, 0.0) + value - a.get(labels, 0.0)
    return out


def host_long_visit_ms_per_step(src):
    """Wall ms a step in visits longer than 8 ms, over every phase in which
    the host works (``driver_clock.WAITING`` left out)."""
    by, steps = delta_by(src, LONG_VISIT_S, "phase"), \
        readers.steps_in_window(src)
    if by is None or not steps:
        return None
    return 1e3 * sum(v for phase, v in by.items()
                     if phase not in driver_clock.WAITING) / steps


def gc_pause_ms_per_step(src):
    secs, steps = readers.delta(src, GC_PAUSE_S), readers.steps_in_window(src)
    if secs is None or not steps:
        return None
    return 1e3 * secs / steps


def gc_full_pause_mean_ms(src):
    """Mean pause of a full collection (generation 2) in the window; 0.0
    where it held none."""
    secs = delta_by(src, GC_PAUSE_S, "generation")
    count = delta_by(src, GC_COLLECTIONS, "generation")
    if secs is None or count is None or "2" not in secs or "2" not in count:
        return None
    return 1e3 * secs["2"] / count["2"] if count["2"] else 0.0


# ---------------------------------------------------------------- the trace
def lay_out(devices, host):
    """The arithmetic, on what ``xplane_reduce.read_xplane`` returns (tests
    hand-make it): per device the gaps between leaf ops, as
    ``timeline.lay_out`` cuts them; idle seconds under each span of
    :data:`NAMED`; the part of the gaps of :data:`LONG_GAP_S` or more that
    lies under none of them; and those gaps one by one, longest first, with
    the milliseconds of each that every span covers. Means over the chips.
    ``seen`` is the names the trace holds at all."""
    starts = [s for d in devices.values() for _, s, _ in d["ops"]]
    ends = [e for d in devices.values() for _, _, e in d["ops"]]
    if not starts:
        return None
    window, n = (min(starts), max(ends)), len(devices)
    spans, outer = {}, {}
    for name, s, e in host:
        if name in NAMED:
            spans.setdefault(name, []).append((s, e))
        elif name in OUTER:
            outer.setdefault(name, []).append((s, e))
    named = [iv for ivs in spans.values() for iv in ivs]
    by_span = dict.fromkeys(NAMED, 0.0)
    unnamed_long_s, long_gaps = 0.0, []
    for d in devices.values():
        leaf = [(s, e) for text, s, e in d["ops"]
                if xplane_reduce.op_kind(text)
                not in xplane_reduce.CONTAINER_KINDS]
        gaps = [g for g in xplane_reduce.gaps(xplane_reduce.merge(leaf),
                                              window)
                if g[1] - g[0] >= xplane_reduce.MIN_GAP_S]
        for name in spans:
            by_span[name] += timeline.overlap_s(gaps, spans[name]) / n
        for gap in gaps:
            if gap[1] - gap[0] < LONG_GAP_S - _NS:
                continue
            bare = sum(e - s for s, e in xplane_reduce.subtract([gap], named))
            unnamed_long_s += bare / n
            under = {name: 1e3 * timeline.overlap_s([gap], ivs)
                     for name, ivs in {**spans, **outer}.items()}
            long_gaps.append({
                "ms": 1e3 * (gap[1] - gap[0]), "unnamed_ms": 1e3 * bare,
                "under_ms": {k: v for k, v in under.items() if v > 0}})
    long_gaps.sort(key=lambda g: -g["ms"])
    return {"idle_by_span_s": by_span, "unnamed_long_s": unnamed_long_s,
            "long_gaps": long_gaps, "seen": set(spans)}


def of(src):
    """The stalls of this run's trace, built once and kept in ``src``; None
    without a device trace."""
    if "stall_trace" not in src:
        src["stall_trace"] = _build(src)
    return src["stall_trace"]


def _build(src):
    x, tl = src.get("xplane"), timeline.of(src)
    if not x or not tl or not tl["steps"]:
        return None
    found = timeline._find_trace(x)
    if found is None:
        return None
    st = lay_out(found[1], found[2])
    if st is None:
        return None
    st["steps"] = len(tl["steps"])
    print(json.dumps({"event": "stalls", "steps_in_trace": st["steps"],
                      "spans_seen": sorted(st["seen"]),
                      "unnamed_long_ms": 1e3 * st["unnamed_long_s"],
                      "long_gaps": st["long_gaps"][:40]}), flush=True)
    return st


def idle_ms_per_step(src, name):
    """Device idle time under the span ``name``, per traced step. A program
    that never opens the span (none in the whole trace, where every step
    would hold one) gives None; ``gc`` is in the program where its counter
    is, and reads 0.0 where the traced seconds held no collection."""
    st = of(src)
    if not st:
        return None
    if name not in st["seen"] and (
            name != "gc" or readers.delta(src, GC_COLLECTIONS) is None):
        return None
    return 1e3 * st["idle_by_span_s"][name] / st["steps"]


def long_gap_unnamed_ms_per_step(src):
    """Device idle in gaps of 5 ms or more under none of :data:`NAMED`, per
    traced step; None for a program without the ``sweep`` span, whose
    steps' first and last stretch nothing names."""
    st = of(src)
    if not st or "sweep" not in st["seen"]:
        return None
    return 1e3 * st["unnamed_long_s"] / st["steps"]
