"""Device time of a decoder-hybrid-decoder model's layers in a traced run, by
the named scopes the program puts on its ops (PR 37): ``ssm_proj`` (a Mamba
layer's four projections), ``ssm_mix`` (its convolution, gates and the two
scan kernels), ``gmu`` (a Gated Memory Unit), ``window_attn`` (a window
layer's projections, kernel call and combine), ``yoco_attn`` (the same for the
middle full layer and the cross layers that read its cache); of two kernels
by their names, ``ssm_recurrent_update`` and ``ssm_chunk_scan``; and of the
ragged kernel's calls by the scope they lie under (``window_attn/ragged``,
``yoco_attn/ragged``): never all Mosaic time, which here holds all three
kernels. Read like ``gdn_trace.scope_seconds``, from the ops' ``op_name`` path
components.

A program without the scopes (another model, a parent commit) gives None and
raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("ssm_proj", "ssm_mix", "gmu", "window_attn", "yoco_attn")
KERNELS = ("ssm_recurrent_update", "ssm_chunk_scan")
RAGGED = "ragged_paged_attention"


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it, kernel name: seconds of
    the ops so named, "<scope>/ragged": seconds of the ragged kernel's calls
    under an attention scope}, mean over chips."""
    out = dict.fromkeys(SCOPES + KERNELS + ("window_attn/ragged",
                                            "yoco_attn/ragged"), 0.0)
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
                    if RAGGED in short and scope + "/ragged" in out:
                        out[scope + "/ragged"] += (e - s) / n
            for kernel in KERNELS:
                if kernel in short:
                    out[kernel] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries a Mamba layer's scope."""
    if "ssm_trace" not in src:
        src["ssm_trace"] = _build(src)
    return src["ssm_trace"]


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
    return secs if secs["ssm_mix"] > 0 or secs["ssm_proj"] > 0 else None


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
    if not args or not all("cross_rows" in a for a in args):
        return None
    return args
