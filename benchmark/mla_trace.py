"""Device time of latent attention and of the shared expert in a traced run,
by the named scopes the program puts on its ops (PR 31): ``mla_attend`` (the
attention kernel), ``mla_proj`` (the latent's down- and up-projections, the
absorption and ``W_o``), ``moe_shared`` (the shared expert, beside ``moe``);
and of the kernel by its name, ``mla_ragged_attention``, never all Mosaic
time: the grouped matmul is a Mosaic kernel too. Read like
``moe_trace.scope_seconds``, from the ops' ``op_name`` path components.

A program without the scopes (a model without latent attention, a parent
commit) gives None and raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("mla_attend", "mla_proj", "moe_shared")
KERNEL = "mla_ragged_attention"


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it, "kernel": seconds of the
    ops named ``KERNEL``, "kernel_calls": their number}, mean over chips."""
    out = dict.fromkeys(SCOPES + ("kernel",), 0.0)
    out["kernel_calls"] = 0
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
            if KERNEL in xplane_reduce.short_name(text):
                out["kernel"] += (e - s) / n
                out["kernel_calls"] += 1
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries a latent-attention scope."""
    if "mla_trace" not in src:
        src["mla_trace"] = _build(src)
    return src["mla_trace"]


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
    return secs if secs["mla_attend"] > 0 or secs["mla_proj"] > 0 else None


def share_of_busy(src, scope):
    """Device time under ``scope`` over device busy time, in percent."""
    secs, x = of(src), src.get("xplane")
    if not secs or not x or not x.get("busy_s"):
        return None
    return 100.0 * secs[scope] / x["busy_s"]
