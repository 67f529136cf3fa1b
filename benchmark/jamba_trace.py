"""Device time of a Jamba model's layers in a traced run, by the named scopes
the program puts on its ops (PR 50): ``jamba_attn`` (an attention layer's
projections, the ragged kernel's call and ``W_o``), ``jamba_mlp`` (every
layer's SwiGLU and the norm before it), and of the ragged kernel's calls
under the attention scope (``jamba_attn/ragged``). The shared mixer's scopes
``ssm_proj`` / ``ssm_mix`` and its two kernels by their names are
``ssm_trace``'s, which reads them of this model's trace as of
Phi-4-mini-flash's. Read like ``ssm_trace.scope_seconds``, from the ops'
``op_name`` path components.

A program without the scopes (another model, a parent commit) gives None and
raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("jamba_attn", "jamba_mlp")
RAGGED = "ragged_paged_attention"


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it, "jamba_attn/ragged":
    seconds of the ragged kernel's calls under the attention scope}, mean
    over chips."""
    out = dict.fromkeys(SCOPES + ("jamba_attn/ragged",), 0.0)
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
            if "jamba_attn" in parts and RAGGED in short:
                out["jamba_attn/ragged"] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries a Jamba layer's scope."""
    if "jamba_trace" not in src:
        src["jamba_trace"] = _build(src)
    return src["jamba_trace"]


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
    return secs if secs["jamba_attn"] > 0 or secs["jamba_mlp"] > 0 else None


def share_of_busy(src, key):
    """Device time under scope ``key`` over device busy time, in percent."""
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
    if not args or not all("scan_tokens" in a and "attn_pairs" in a
                           for a in args):
        return None
    return args
