"""Device time of a one-mixer-a-block model's blocks in a traced run, by the
named scopes the program puts on its ops (PR 47): ``ssd_proj`` (a Mamba-2
block's two projections), ``ssd_mix`` (its convolution, gates, the two
kernels and the gated norm), ``mixer_attn`` (an attention block's
projections, kernel call and output projection); and of two kernels by their
names, ``ssd_recurrent_update`` and ``ssd_chunk_scan``. Read like
``ssm_trace.scope_seconds``, from the ops' ``op_name`` path components. The
routed FFNs' scopes (``moe_route`` / ``moe_experts`` / ``moe_shared``) are
``moe_trace``'s and ``mla_trace``'s.

A program without the scopes (another model, a parent commit) gives None and
raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("ssd_proj", "ssd_mix", "mixer_attn")
KERNELS = ("ssd_recurrent_update", "ssd_chunk_scan")


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
            short = xplane_reduce.short_name(text)
            for scope in SCOPES:
                if scope in parts:
                    out[scope] += (e - s) / n
            for kernel in KERNELS:
                if kernel in short:
                    out[kernel] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries a Mamba-2 block's scope."""
    if "ssd_trace" not in src:
        src["ssd_trace"] = _build(src)
    return src["ssd_trace"]


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
    return secs if secs["ssd_mix"] > 0 or secs["ssd_proj"] > 0 else None


def share_of_busy(src, key):
    """Device time under scope (or of kernel) ``key`` over device busy time,
    in percent."""
    secs, x = of(src), src.get("xplane")
    if not secs or not x or not x.get("busy_s"):
        return None
    return 100.0 * secs[key] / x["busy_s"]


def traced_dispatch_args(src):
    """The ``dispatch`` spans' args of exactly the traced steps, where they
    carry this model's counts; else None."""
    tl = timeline.of(src)
    if not tl or not tl["steps"]:
        return None
    args = timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]})
    if not args or not all("ssd_update_rows" in a for a in args):
        return None
    return args
