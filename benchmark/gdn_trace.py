"""Device time of a hybrid model's linear (Gated DeltaNet) layers in a traced
run, by the named scopes the program puts on its ops (PR 33): ``gdn_mix`` (the
convolution, the gates, the two kernels, the gated norm) and ``gdn_proj`` (the
input projections and ``W_o``); and of three kernels by their names,
``gdn_recurrent_update``, ``gdn_chunk_scan`` and ``ragged_paged_attention``,
never all Mosaic time, which here holds all three. Read like
``mla_trace.scope_seconds``, from the ops' ``op_name`` path components.

A program without the scopes (a model without such layers, a parent commit)
gives None and raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("gdn_mix", "gdn_proj")
KERNELS = ("gdn_recurrent_update", "gdn_chunk_scan",
           "ragged_paged_attention")


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it, kernel name: seconds of
    the ops so named}, mean over chips."""
    out = dict.fromkeys(SCOPES + KERNELS, 0.0)
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
            short = xplane_reduce.short_name(text)
            for kernel in KERNELS:
                if kernel in short:
                    out[kernel] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries a linear layer's scope."""
    if "gdn_trace" not in src:
        src["gdn_trace"] = _build(src)
    return src["gdn_trace"]


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
    return secs if secs["gdn_mix"] > 0 or secs["gdn_proj"] > 0 else None


def share_of_busy(src, key):
    """Device time under scope (or of kernel) ``key`` over device busy time,
    in percent."""
    secs, x = of(src), src.get("xplane")
    if not secs or not x or not x.get("busy_s"):
        return None
    return 100.0 * secs[key] / x["busy_s"]


def traced_dispatch_args(src):
    """The ``dispatch`` spans' args of exactly the traced steps, where they
    carry a linear layer's counts; else None."""
    tl = timeline.of(src)
    if not tl or not tl["steps"]:
        return None
    args = timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]})
    if not args or not all("state_rows" in a for a in args):
        return None
    return args
