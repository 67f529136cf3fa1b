"""Device time of learned sparse attention in a traced run, by the named
scopes the program puts on its ops (PR 43), all under ``mla``:
``dsa_index_proj`` (an indexer's three projections, the key's LayerNorm, the
rotation, the index-key write), ``dsa_index_score`` (the scores kernel),
``dsa_select`` (the k-th value search and the selection's layout),
``dsa_attend`` (the attention over the selection, in place of
``mla_attend``); and of the two kernels by their names, ``dsa_index_scores``
and ``dsa_attention``, never all Mosaic time: the grouped matmuls are Mosaic
kernels too. Read like ``mla_trace.scope_seconds``, from the ops' ``op_name``
path components. Beside it, the ``dispatch`` spans' counts of exactly the
traced steps.

A program without the scopes (another model, a parent commit) gives None and
raises nothing.
"""
import timeline
import xplane_reduce

SCOPES = ("dsa_index_proj", "dsa_index_score", "dsa_select", "dsa_attend")
KERNELS = ("dsa_index_scores", "dsa_attention")
COUNTS = ("index_query_rows", "index_key_rows", "selected_rows",
          "attended_rows")


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
                # ("dsa_attention" is no part of "dsa_index_scores")
                if kernel in short:
                    out[kernel] += (e - s) / n
    return out


def of(src):
    """``scope_seconds`` of this run's trace, kept in ``src``; None without
    a device trace or when no op carries a sparse-attention scope."""
    if "dsa_trace" not in src:
        src["dsa_trace"] = _build(src)
    return src["dsa_trace"]


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
    return secs if secs["dsa_attend"] > 0 or secs["dsa_index_score"] > 0 \
        else None


def share_of_busy(src, *scopes):
    """Device time under ``scopes`` over device busy time, in percent."""
    secs, x = of(src), src.get("xplane")
    if not secs or not x or not x.get("busy_s"):
        return None
    return 100.0 * sum(secs[s] for s in scopes) / x["busy_s"]


def counted(args):
    """The sums of ``COUNTS`` (and ``attn_pairs``, ``kv_tokens``, the live
    tokens) over ``dispatch`` args that carry them; None where none does."""
    args = [a for a in args or () if "selected_rows" in a]
    if not args:
        return None
    out = {k: sum(a[k] for a in args)
           for k in COUNTS + ("attn_pairs", "kv_tokens")}
    out["query_tokens"] = sum(a.get("decode_tokens", 0)
                              + a.get("prefill_tokens", 0) for a in args)
    return out


def traced_counts(src):
    """``counted`` of exactly the traced steps' ``dispatch`` spans."""
    tl = timeline.of(src)
    if not tl or not tl["steps"]:
        return None
    return counted(timeline.dispatch_args(src, {n for n, _, _ in tl["steps"]}))


def window_counts(src):
    """``counted`` of the window's ``dispatch`` spans."""
    return counted(timeline.dispatch_args(src))
