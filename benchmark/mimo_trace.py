"""Device time of a MiMo-V2-Flash model's layers in a traced run, by the named
scopes the program puts on its ops (PR 56 runs these layers under the scopes
the other models' readers know): ``attn`` (a full-attention mixer: the leading
dense layer's and a period's full layer's: projections, the partial rotation,
the pool's write, the ragged kernel's call, ``W_o``, the norm before it and
the residual add), ``window_attn`` (a window mixer likewise: the ring's write
in place of the pool's, the kernel under the window with the sink), ``moe``
(the six routed FFNs; ``moe_trace`` reads what lies inside it), ``mlp`` (the
dense layer's SwiGLU) and ``lm_head``; of the ragged kernel's calls by the scope they lie under (``attn/ragged`` over the
pool at 64 / 4 heads, ``window_attn/ragged`` over the rings at 64 / 8: one
kernel name, two row shapes); and ``unscoped``, the time of leaf ops under
none of the scopes above (the embedding gather, the final norm, sampling, what
XLA fused across a scope's edge). Read like ``ssm_trace.scope_seconds``, from
the ops' ``op_name`` path components.

A Phi-4-mini-flash trace carries ``window_attn`` too and a routed model's
``moe``; this model's alone carries BOTH, which is what ``of`` asks for. A
program without them (another model, a parent commit) gives None and raises
nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("attn", "window_attn", "moe", "mlp", "lm_head")
RAGGED = "ragged_paged_attention"


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it, "<scope>/ragged": seconds
    of the ragged kernel's calls under an attention scope, "unscoped": seconds
    under none of ``SCOPES``}, mean over chips."""
    out = dict.fromkeys(SCOPES + ("attn/ragged", "window_attn/ragged",
                                  "unscoped"), 0.0)
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
            if not any(scope in parts for scope in SCOPES):
                out["unscoped"] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or unless ops carry both ``window_attn`` and ``moe``."""
    if "mimo_trace" not in src:
        src["mimo_trace"] = _build(src)
    return src["mimo_trace"]


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
    return secs if secs["window_attn"] > 0 and secs["moe"] > 0 else None


def share_of_busy(src, key):
    """Device time under the scope ``key`` over device busy time, in
    percent."""
    secs, x = of(src), src.get("xplane")
    if not secs or not x or not x.get("busy_s"):
        return None
    return 100.0 * secs[key] / x["busy_s"]


def traced_dispatch_args(src):
    """The ``dispatch`` spans' args of exactly the traced steps, where they
    carry this model's counts (a window layer's call beside a full
    layer's); else None."""
    tl = timeline.of(src)
    if not tl or not tl["steps"]:
        return None
    args = timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]})
    if not args or not all("window_attn_pairs" in a and "attn_pairs" in a
                           for a in args):
        return None
    return args
