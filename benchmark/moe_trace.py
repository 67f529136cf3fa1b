"""Device time of the routed (mixture-of-experts) FFN in a traced run, by the
named scopes the program puts on its ops (PR 26): ``moe`` around the whole
routed FFN, ``moe_route`` inside it around the router, top-k, ordering,
gather and weighted sum, ``moe_experts`` around the grouped matmuls. The
scopes are path components of an op's ``op_name`` (``tf_op`` in the trace),
read from the trace file by ``timeline.read_extras``.

Beside it, the routing the engine counted for exactly the traced steps: the
``moe_*`` args on the ``device-wait`` span of a unified step and on the
``prefill_launch`` span of a whole-prompt prefill, matched by step number
to the ``step`` annotations of the trace as ``timeline.dispatch_args`` does.

A program without the scopes or the args (a dense model, a parent commit)
gives None everywhere and raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("moe", "moe_route", "moe_experts")
COUNTS = ("moe_pairs", "moe_experts_touched", "moe_max_expert_pairs",
          "moe_layer_calls")


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it}, mean over the chips;
    ``devices`` and ``op_names`` as ``xplane_reduce.read_xplane`` and
    ``timeline.read_extras`` return them."""
    out = dict.fromkeys(SCOPES, 0.0)
    n = max(len(devices), 1)
    for plane, d in devices.items():
        names = op_names.get(plane, {})
        for text, s, e in d["ops"]:
            if xplane_reduce.op_kind(text) in xplane_reduce.CONTAINER_KINDS:
                continue
            parts = (names.get(text) or "").split("/")
            for scope in SCOPES:
                if scope in parts:
                    out[scope] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries the ``moe`` scope."""
    if "moe_trace" not in src:
        src["moe_trace"] = _build(src)
    return src["moe_trace"]


def _build(src):
    x = src.get("xplane")
    if not x or not timeline.of(src):
        return None
    found = timeline._find_trace(x)
    if found is None:
        return None
    path, devices, _host = found
    try:
        extras = timeline.read_extras(path)
    except (ValueError, IndexError):
        return None
    secs = scope_seconds(devices, extras["op_names"])
    return secs if secs["moe"] > 0 else None


def counted(src):
    """{count: total over the layer calls of the traced steps} from the
    spans' args, or None."""
    tl, doc = timeline.of(src), src.get("span_export")
    if not tl or not tl["steps"] or not doc:
        return None
    return sum_counts(doc["traceEvents"], {n for n, _, _ in tl["steps"]})


def sum_counts(events, steps=None):
    """Totals of the ``moe_*`` span args: of every span, or with ``steps``
    (a set of step numbers) of the spans inside exactly those ``step``
    spans."""
    if steps is not None:
        inside = [(e["ts"], e["ts"] + e["dur"]) for e in events
                  if e.get("name") == "step" and e.get("ph") == "X"
                  and e.get("args", {}).get("step") in steps]
        events = [e for e in events
                  if any(lo <= e.get("ts", -1) < hi for lo, hi in inside)]
    rows = [e["args"] for e in events
            if e.get("ph") == "X" and "moe_pairs" in e.get("args", {})]
    if not rows:
        return None
    return {k: sum(r[k] for r in rows) for k in COUNTS}
