"""One timeline for a traced run: the device's idle gaps laid under the
engine's and the gateway's own spans, the ragged kernel's counted work for
exactly the traced steps, and the device time of a training step by phase.

What makes this possible is in the program (PR 24): while the span tracer
records, every engine- and gateway-lane span is also a
``jax.profiler.TraceAnnotation`` of the same name, so the run's
``.xplane.pb`` carries ``step``, ``admit``, ``plan``, ``launch`` (with
``dispatch`` and ``device-wait`` inside), ``host-accept`` and ``loop`` on
the device trace's clock; the ``step`` annotation carries the step number,
which matches it to the ``step`` span of ``/debug/trace``; every
``pallas_call`` has a name, and the training step's ops carry ``optimizer``
and ``loss`` scopes in their ``op_name``. A program without these (a parent
commit) gives a timeline with no spans and no phases: every reader here then
returns None and raises nothing.

The trace is read again from disk (``proc.RUN_DIR/<cell>/xplane``) with
``xplane_reduce.read_xplane``; a trace whose window and busy time differ from
``src["xplane"]`` is not this run's and is refused. Two things that reader
drops are taken from the file's own bytes by a small protobuf walk (no
TensorFlow, no backend): the ``step`` annotations' step numbers and each
device op's ``tf_op`` (the ``op_name`` of its HLO instruction).
"""
import glob
import json
import os

import proc
import readers
import xplane_reduce

#: spans a device gap is attributed to: disjoint by construction (``admit``
#: then ``plan``, ``dispatch`` then ``device-wait``, ``loop`` between steps)
LEAF_SPANS = ("admit", "plan", "dispatch", "device-wait", "host-accept",
              "loop")
#: their enclosing spans: idle under these and under no leaf is the engine's
#: own bookkeeping between phases
OUTER_SPANS = ("step", "launch")
#: every span a gap may lie under: what names a gap of ``breakdown.idle_gaps``
SPAN_NAMES = LEAF_SPANS + OUTER_SPANS
KERNEL_NAMES = ("ragged_paged_attention", "paged_decode_attention",
                "decode_attention", "fused_decode_tick", "flash_fwd",
                "flash_bwd_dkv", "flash_bwd_dq")
PHASES = ("fwd", "remat", "bwd", "opt", "other")


# ----------------------------------------------------- protobuf wire format
def _fields(buf):
    """(field number, value) of one message: varints as ints, 64-bit and
    32-bit fields as raw bytes, length-delimited fields as memoryviews."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        kind = key & 7
        if kind == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
        elif kind == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            val = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            val = bytes(buf[i:i + width])
            i += width
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, val


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key = value = None
    for no, val in _fields(view):
        if no == 1:
            key = val
        elif no == 2:
            value = val
    return key, value


def _stats(view_list, stat_names):
    """{stat name: int or str} of a list of XStat messages."""
    out = {}
    for view in view_list:
        name = value = None
        for no, val in _fields(view):
            if no == 1:
                name = stat_names.get(val)
            elif no in (3, 4):                  # uint64, int64
                value = val
            elif no == 5:                       # str
                value = _text(val)
            elif no == 7:                       # a reference to a stat name
                value = stat_names.get(val)
        if name is not None and value is not None:
            out[name] = value
    return out


def read_extras(path, step_name="step"):
    """What ``xplane_reduce.read_xplane`` drops: ``steps``, the host plane's
    ``step`` annotations as (step number, start s, end s), and ``op_names``,
    {device plane: {instruction text: op_name}}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    steps, op_names = [], {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, lines, metas, stat_names = "", [], {}, {}
        for no2, val in _fields(plane):
            if no2 == 2:
                name = _text(val)
            elif no2 == 3:
                lines.append(val)
            elif no2 == 4:
                k, v = _map_entry(val)
                metas[k] = v
            elif no2 == 5:
                k, v = _map_entry(val)
                stat_names[k] = next(
                    (_text(x) for n3, x in _fields(v) if n3 == 2), "")
        device = xplane_reduce.DEVICE_PLANE.match(name)
        if not device and name != xplane_reduce.HOST_PLANE:
            continue
        meta_name, meta_stats = {}, {}
        for k, view in metas.items():
            stats = []
            for n3, x in _fields(view):
                if n3 == 2:
                    meta_name[k] = _text(x)
                elif n3 == 5:
                    stats.append(x)
            meta_stats[k] = stats
        if device:
            ops = {}
            for k, text in meta_name.items():
                op = _stats(meta_stats[k], stat_names).get("tf_op")
                if op:
                    ops[text] = op
            op_names[name] = ops
            continue
        step_ids = {k for k, text in meta_name.items() if text == step_name}
        for line in lines:
            t0_ns, events = 0, []
            for n3, x in _fields(line):
                if n3 == 3:
                    t0_ns = x
                elif n3 == 4:
                    events.append(x)
            for ev in events:
                meta = off_ps = dur_ps = 0
                stats = []
                for n4, x in _fields(ev):
                    if n4 == 1:
                        meta = x
                    elif n4 == 2:
                        off_ps = x
                    elif n4 == 3:
                        dur_ps = x
                    elif n4 == 4:
                        stats.append(x)
                if meta not in step_ids:
                    continue
                number = _stats(stats, stat_names).get("step")
                if number is None:
                    continue
                start = t0_ns * 1e-9 + off_ps * 1e-12
                steps.append((int(number), start, start + dur_ps * 1e-12))
    return {"steps": sorted(steps), "op_names": op_names}


# ------------------------------------------------------------ the timeline
def _close(a, b):
    return abs(a - b) <= 1e-6 * max(abs(a), abs(b), 1e-9)


def _find_trace(x):
    """(path, devices, host) of the trace under ``proc.RUN_DIR`` whose
    summary is ``x``, newest first; None when there is none."""
    paths = glob.glob(os.path.join(proc.RUN_DIR, "*", "xplane", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        try:
            devices, host = xplane_reduce.read_xplane(path)
        except Exception:                       # not a trace JAX can read
            continue
        s = xplane_reduce.summarize(devices)
        if s and _close(s["window_s"], x["window_s"]) \
                and _close(s["busy_s"], x["busy_s"]):
            return path, devices, host
    return None


def overlap_s(gaps, spans):
    """Seconds of ``gaps`` that some interval of ``spans`` covers."""
    if not spans:
        return 0.0
    return xplane_reduce.union_length(gaps) - sum(
        e - s for s, e in xplane_reduce.subtract(gaps, spans))


def phase_of(op_name):
    """The training phase an op's ``op_name`` places it in."""
    if not op_name:
        return "other"
    if "/optimizer/" in op_name:
        return "opt"
    if "rematted_computation" in op_name:
        return "remat"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "other"


def lay_out(devices, host, extras):
    """The arithmetic, on what the two readers return (tests hand-make it)."""
    starts = [s for d in devices.values() for _, s, _ in d["ops"]]
    ends = [e for d in devices.values() for _, _, e in d["ops"]]
    if not starts:
        return None
    window = (min(starts), max(ends))
    n = len(devices)
    idle_s, by_span, by_kernel = 0.0, {}, {}
    by_phase = dict.fromkeys(PHASES, 0.0)
    spans = {}
    for name, s, e in host:
        if name in SPAN_NAMES:
            spans.setdefault(name, []).append((s, e))
    named = [iv for ivs in spans.values() for iv in ivs]
    under_any = 0.0
    for plane, d in devices.items():
        leaf = []
        ops = extras["op_names"].get(plane, {})
        for text, s, e in d["ops"]:
            if xplane_reduce.op_kind(text) in xplane_reduce.CONTAINER_KINDS:
                continue
            leaf.append((s, e))
            by_phase[phase_of(ops.get(text))] += (e - s) / n
            kernel = xplane_reduce.short_name(text).split(".")[0]
            if kernel in KERNEL_NAMES:
                rec = by_kernel.setdefault(kernel, {"count": 0,
                                                    "seconds": 0.0})
                rec["count"] += 1
                rec["seconds"] += (e - s) / n
        gaps = [g for g in xplane_reduce.gaps(xplane_reduce.merge(leaf),
                                              window)
                if g[1] - g[0] >= xplane_reduce.MIN_GAP_S]
        idle_s += sum(e - s for s, e in gaps) / n
        under_any += overlap_s(gaps, named) / n
        for name in LEAF_SPANS:
            by_span[name] = by_span.get(name, 0.0) \
                + overlap_s(gaps, spans.get(name, [])) / n
    steps = [st for st in extras["steps"]
             if window[0] <= (st[1] + st[2]) / 2 < window[1]]
    return {"window": window, "idle_s": idle_s, "idle_named_s": under_any,
            "idle_by_span_s": by_span if spans else {},
            "steps": steps, "kernels": by_kernel,
            "phase_s": by_phase if any(extras["op_names"].values()) else {}}


def clock_offsets(src, tl):
    """Device-trace clock minus tracer clock, in seconds, one per ``step``
    span of ``/debug/trace`` whose twin (same step number) is in the trace."""
    doc = src.get("span_export")
    if not doc:
        return []
    twin = {n: s for n, s, _ in tl["steps"]}
    return [twin[e["args"]["step"]] - e["ts"] * 1e-6
            for e in doc["traceEvents"]
            if e.get("name") == "step" and e.get("ph") == "X"
            and e.get("args", {}).get("step") in twin]


def of(src):
    """The timeline of this run, built once and kept in ``src`` beside the
    other sources; None without a device trace."""
    if "timeline" not in src:
        src["timeline"] = _build(src)
    return src["timeline"]


def _build(src):
    x = src.get("xplane")
    if not x:
        return None
    found = _find_trace(x)
    if found is None:
        return None
    path, devices, host = found
    try:
        extras = read_extras(path)
    except (ValueError, IndexError):    # bytes this walk cannot follow
        extras = {"steps": [], "op_names": {}}
    tl = lay_out(devices, host, extras)
    if tl is None:
        return None
    offs = clock_offsets(src, tl)
    n = max(len(tl["steps"]), 1)
    print(json.dumps({
        "event": "timeline", "steps_in_trace": len(tl["steps"]),
        "idle_ms": 1e3 * tl["idle_s"],
        "idle_named_ms": 1e3 * tl["idle_named_s"],
        "idle_by_span_ms_per_step": {k: 1e3 * v / n for k, v
                                     in tl["idle_by_span_s"].items()},
        "kernels_by_name": tl["kernels"], "phase_s": tl["phase_s"],
        "clock_offset_s": sorted(offs)[len(offs) // 2] if offs else None,
        "clock_offset_spread_us": 1e6 * (max(offs) - min(offs))
        if offs else None}), flush=True)
    return tl


# ------------------------------------------------- what the metric files use
def idle_ms_per_step(src, names):
    """Device idle time under the spans ``names``, per traced step."""
    tl = of(src)
    if not tl or not tl["steps"] or not tl["idle_by_span_s"]:
        return None
    return 1e3 * sum(tl["idle_by_span_s"].get(n, 0.0) for n in names) \
        / len(tl["steps"])


def phase_share(src, phase):
    """Device time of the ops of one training phase over that of all ops
    (so the five phases sum to 100); None for a program whose update
    carries no ``optimizer`` scope."""
    tl = of(src)
    if not tl or not tl.get("phase_s", {}).get("opt"):
        return None     # no ``optimizer`` scope: "other" holds the update
    total = sum(tl["phase_s"].values())
    return 100.0 * tl["phase_s"][phase] / total if total else None


def step_tokens_per_step(src, kind):
    """Delta of ``serving_step_tokens_total{kind=...}`` over delta steps."""
    md, steps = src.get("metrics_delta"), readers.steps_in_window(src)
    if not md or not steps:
        return None
    label = '{kind="%s"}' % kind
    a = md["start"].get("serving_step_tokens_total", {}).get(label)
    b = md["end"].get("serving_step_tokens_total", {}).get(label)
    if a is None or b is None:
        return None
    return (b - a) / steps


def dispatch_args(src, steps=None):
    """The ``dispatch`` spans' args in the window; with ``steps`` (a set of
    step numbers) those of exactly these steps, wherever the window lies."""
    doc = src.get("span_export")
    if steps is None:
        spans = readers.window_spans(src)
    elif doc:
        inside = [(e["ts"], e["ts"] + e["dur"]) for e in doc["traceEvents"]
                  if e.get("name") == "step" and e.get("ph") == "X"
                  and e.get("args", {}).get("step") in steps]
        spans = [e for e in doc["traceEvents"]
                 if any(lo <= e.get("ts", -1) < hi for lo, hi in inside)]
    else:
        spans = None
    if not spans:
        return None
    out = [e["args"] for e in spans if e.get("name") == "dispatch"
           and "grid_steps" in e.get("args", {})]
    return out or None
