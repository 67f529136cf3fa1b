"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time as a union of intervals, idle share, the ops with
most device time, the longest idle gaps and what the host was doing in them,
Mosaic (Pallas) kernel time by call signature, and collective time with the
part of it during which no compute ran on that device.

Written against a trace of one TPU v5e (``tests/data/small_v5e.xplane.pb``,
recorded by ``tests/data/record_trace.py``). What that showed:

- each chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
  event per executed HLO instruction, named by the instruction's full text
  (``%fusion.3 = bf16[...] fusion(...), kind=kOutput, ...``); ``XLA
  Modules`` holds one event per program execution; ``Async XLA Ops`` holds
  the start-to-done span of asynchronous ops (copies, collectives);
- a Pallas kernel is a ``custom-call`` whose text carries
  ``custom_call_target="tpu_custom_call"``; its instruction name is not the
  kernel's (no ``name=`` is set on any ``pallas_call``), so kernels are told
  apart by the shapes in the text, the *signature*;
- host threads are lines of the plane ``/host:CPU``; a
  ``jax.profiler.TraceAnnotation`` is an event on its thread's line. Host
  and device share one time base to within a millisecond or two.

The arithmetic (``union_length``, ``subtract``, ``summarize``) works on
plain ``(start, end)`` pairs and is tested on hand-made lists.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute",
                    "collective-broadcast")
CONTAINER_KINDS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
TOP_N = 10
MIN_GAP_S = 1e-6        # shorter gaps are the trace's own rounding


# ------------------------------------------------------------- interval math
def merge(intervals):
    """Sorted disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals):
    return sum(e - s for s, e in merge(intervals))


def subtract(a, b):
    """The part of ``a`` (merged) that no interval of ``b`` covers."""
    out, b = [], merge(b)
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, window):
    return subtract([window], busy)


# ------------------------------------------------------------------ op names
def short_name(text):
    """``%fusion.3 = ...`` -> ``fusion.3``; other names pass through."""
    if text.startswith("%"):
        return text[1:].split(" = ", 1)[0]
    return text.split(" ", 1)[0]


def op_kind(text):
    """The HLO opcode of an instruction text, or the name without its
    numeric suffix when the text is only a name."""
    if " = " in text:
        rest = text.split(" = ", 1)[1]
        depth = 0
        for i, ch in enumerate(rest):      # skip the (possibly tuple) type
            if ch in "([{":
                depth += 1
            elif ch in ")]}":
                depth -= 1
            elif ch == " " and depth == 0:
                m = re.match(r"([\w\-]+)\(", rest[i + 1:])
                if m:
                    return m.group(1)
        return short_name(text)
    return re.sub(r"\.\d+$", "", short_name(text))


def is_collective(kind):
    return kind.startswith(COLLECTIVE_KINDS)


def mosaic_signature(text):
    """``out|out <- operand,operand`` with layouts stripped, e.g.
    ``bf16[8,1024,128]|f32[8,1024,1] <- bf16[8,1024,128],...``."""
    head, _, tail = text.partition(" custom-call(")
    outs = [f"{t}[{d}]" for t, d in _SHAPE.findall(head.split(" = ", 1)[-1])]
    args = [f"{t}[{d}]" for t, d in
            _SHAPE.findall(tail.split("), custom_call_target", 1)[0])]
    return "|".join(outs) + " <- " + ",".join(args)


# ---------------------------------------------------------------- reductions
def summarize(devices, host_spans=(), annotations=()):
    """``devices``: {plane: {"ops": [(text, start, end)], "async":
    [(text, start, end)]}} in seconds. ``host_spans``: [(name, start, end)].
    ``annotations``: host span names a gap may be attributed to.

    The traced window runs from the first to the last device event of any
    chip, so idle time before the first or after the last op is not seen.
    Numbers are means over the chips."""
    starts = [s for d in devices.values() for _, s, _ in d["ops"]]
    ends = [e for d in devices.values() for _, _, e in d["ops"]]
    if not starts:
        return None
    window = (min(starts), max(ends))
    n = len(devices)
    busy_s = mosaic_s = coll_s = exposed_s = 0.0
    by_op, by_sig, all_gaps = {}, {}, []
    for plane, d in sorted(devices.items()):
        leaf, compute, coll = [], [], []
        for text, s, e in d["ops"]:
            kind = op_kind(text)
            if kind in CONTAINER_KINDS:
                continue
            leaf.append((s, e))
            name = short_name(text)
            if MOSAIC_MARK in text:
                sig = mosaic_signature(text)
                rec = by_sig.setdefault(sig, {"count": 0, "seconds": 0.0})
                rec["count"] += 1
                rec["seconds"] += e - s
                mosaic_s += e - s
                name = "mosaic:" + name
            by_op[name] = by_op.get(name, 0.0) + (e - s)
            (coll if is_collective(kind) else compute).append((s, e))
        coll += [(s, e) for text, s, e in d.get("async", ())
                 if is_collective(op_kind(text))]
        busy = merge(leaf)
        busy_s += sum(e - s for s, e in busy)
        coll_s += union_length(coll)
        exposed_s += sum(e - s for s, e in subtract(coll, compute))
        all_gaps += [(e - s, s, e, plane) for s, e in gaps(busy, window)
                     if e - s >= MIN_GAP_S]
    all_gaps.sort(reverse=True)
    named = []
    for length, s, e, _plane in all_gaps[:TOP_N]:
        # the span that covers most of the gap, and half of it or more; of
        # nested spans that cover it alike (``step`` > ``launch`` >
        # ``dispatch``), the innermost
        label, best = "host, unattributed", (0.0, 0.0)
        for name, hs, he in host_spans:
            if name in annotations:
                rank = (min(e, he) - max(s, hs), hs - he)
                if 2 * rank[0] >= length and rank > best:
                    label, best = name, rank
        named.append([label, length])
    ann = {}
    for name, hs, he in host_spans:
        if name in annotations:
            rec = ann.setdefault(name, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += he - hs
    width = window[1] - window[0]
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    return {
        "devices": n, "window_s": width, "busy_s": busy_s / n,
        "idle_share": 1.0 - busy_s / n / width if width > 0 else None,
        "mosaic_s": mosaic_s / n, "mosaic_calls": by_sig,
        "collective_s": coll_s / n, "collective_exposed_s": exposed_s / n,
        "top_ops": [[name, sec / n] for name, sec in top],
        "gaps": named, "annotations": ann,
    }


def read_xplane(path):
    """(devices, host_spans) of one ``.xplane.pb``, times in seconds."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            d = {"ops": [], "async": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", ASYNC_LINE: "async"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    d[key].append((ev.name, s, s + ev.duration_ns * 1e-9))
            devices[plane.name] = d
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and not ev.name.startswith("$"):
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return devices, host


def newest_trace(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def reduce_dir(trace_dir, annotations=()):
    """The summary of the newest trace under ``trace_dir``; None when there
    is none or no operation ran on a device."""
    path = newest_trace(trace_dir)
    if path is None:
        return None
    devices, host = read_xplane(path)
    return summarize(devices, host, annotations)
