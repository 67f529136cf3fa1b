"""Device time of a Qwen3-Next model's layers in a traced run, by the named
scopes the program puts on its ops (PR 54 runs these layers under the scopes
the other models' readers know): ``gdn`` (a Gated DeltaNet mixer: its
projections, convolution, gates, the two kernels, the gated norm, the norm
before it and the residual add), ``attn`` (a gated full-attention mixer
likewise), ``moe`` (every layer's routed FFN) with ``moe_experts`` (the three
grouped matmuls) inside it, ``moe_shared`` (the gated shared expert, beside
``moe``) and ``lm_head``; of three kernels by their names,
``gdn_recurrent_update``, ``gdn_chunk_scan`` and ``ragged_paged_attention``;
and ``unscoped``, the time of leaf ops under none of the scopes above (the
embedding gather, the final norm, sampling, what XLA fused across a scope's
edge). Read like ``gdn_trace.scope_seconds``, from the ops' ``op_name`` path
components.

Both hybrids' traces carry ``gdn``; this model's alone carries ``gdn`` AND
``moe``, which is what ``of`` asks for. A program without them (another
model, a parent commit) gives None and raises nothing.
"""
import moe_trace
import timeline
import xplane_reduce

SCOPES = ("gdn", "attn", "moe", "moe_experts", "moe_shared", "lm_head")
TOP = ("gdn", "attn", "moe", "moe_shared", "lm_head")
KERNELS = ("gdn_recurrent_update", "gdn_chunk_scan",
           "ragged_paged_attention")


def scope_seconds(devices, op_names):
    """{scope: device seconds of leaf ops under it, kernel name: seconds of
    the ops so named, "unscoped": seconds under none of ``TOP``}, mean over
    chips."""
    out = dict.fromkeys(SCOPES + KERNELS + ("unscoped",), 0.0)
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
            if not any(scope in parts for scope in TOP):
                out["unscoped"] += (e - s) / n
            short = xplane_reduce.short_name(text)
            for kernel in KERNELS:
                if kernel in short:
                    out[kernel] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or unless ops carry both ``gdn`` and ``moe``."""
    if "qwen3_next_trace" not in src:
        src["qwen3_next_trace"] = _build(src)
    return src["qwen3_next_trace"]


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
    return secs if secs["gdn"] > 0 and secs["moe"] > 0 else None


def share_of_busy(src, *keys):
    """Device time under the scopes ``keys`` (summed) over device busy time,
    in percent."""
    secs, x = of(src), src.get("xplane")
    if not secs or not x or not x.get("busy_s"):
        return None
    return 100.0 * sum(secs[k] for k in keys) / x["busy_s"]


def traced_dispatch_args(src):
    """The ``dispatch`` spans' args of exactly the traced steps, where they
    carry this model's counts; else None."""
    tl = timeline.of(src)
    if not tl or not tl["steps"]:
        return None
    args = timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]})
    if not args or not all("state_rows" in a and "attn_pairs" in a
                           for a in args):
        return None
    return args


def traced_moe_counts(src):
    """The routing the engine counted for exactly the traced steps
    (``moe_trace.counted``: pairs on held experts and held experts touched,
    summed over the layer calls), where this model's trace is there."""
    return moe_trace.counted(src) if of(src) else None
