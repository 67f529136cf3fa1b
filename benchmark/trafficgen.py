"""The one traffic generator: a mix is a data file under ``traffic/`` and
this module turns it, with a seed, into a schedule of requests (serving) or
a dataset of token ids (training). A new mix is a new file, never new code.

Steadiness: every draw is *stratified*. ``n`` samples of a distribution are
its quantiles at ``(i + u_i) / n`` in a seeded random order, so every seed
offers the same multiset of lengths and the same number of arrivals per
unit of time up to the jitter inside a stratum, and only their order
changes. The marginal distribution is the one the file names; what is
removed is the seed-to-seed swing in total work, which would otherwise be
most of a metric's spread at the tens of requests a window holds. For
arrivals that also removes the burstiness of a Poisson process, so the
process is named for what it is (``"arrivals": "stratified-exponential"``);
a mix with independent or bursty gaps brings its own branch here.

Stdlib and ``random`` only: the parent process imports no JAX and no numpy.
"""
import math
import random
from statistics import NormalDist

_NORMAL = NormalDist()


def stratified_uniforms(n, rng, block=None):
    """n numbers in (0, 1). Every run of ``block`` consecutive numbers (all
    n when ``block`` is None) holds one from each of its equal strata, in
    random order: any stretch of the schedule covers the distribution."""
    out = []
    while len(out) < n:
        m = min(block or n, n - len(out))
        us = [(i + rng.random()) / m for i in range(m)]
        rng.shuffle(us)
        out.extend(us)
    return [min(max(u, 1e-9), 1 - 1e-9) for u in out]


def quantile(spec, u):
    """The u-quantile of the length distribution a mix file describes,
    clipped to ``[min, max]`` and rounded to a whole number of tokens."""
    dist = spec["dist"]
    if dist == "constant":
        return int(spec["value"])
    if dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return int(round(min(max(x, spec["min"]), spec["max"])))


def lengths(spec, n, rng, block=None):
    return [quantile(spec, u) for u in stratified_uniforms(n, rng, block)]


def arrival_times(mix, horizon_s, rng):
    """Due times (seconds from the generator's start) of an open loop."""
    rate = float(mix["rate_per_s"])
    n = max(int(math.ceil(rate * horizon_s)), 1)
    kind = mix["arrivals"]
    if kind == "stratified-exponential":
        gaps = [-math.log(1.0 - u) / rate for u in
                stratified_uniforms(n, rng, mix.get("stratify_block"))]
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    t, out = 0.0, []
    for g in gaps:
        t += g
        out.append(t)
    return out


def serve_schedule(mix, seed, horizon_s, vocab_size):
    """Every request a serving run may send, in order. An open loop gets a
    ``due`` time; a closed loop gets ``due = None`` and is sent when a
    client is free. Prompts are unshared uniform random token ids."""
    rng = random.Random(f"schedule/{seed}")
    if mix["loop"] == "open":
        dues = arrival_times(mix, horizon_s, rng)
    elif mix["loop"] == "closed":
        dues = [None] * int(mix["max_requests"])
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    n = len(dues)
    block = mix.get("stratify_block")
    plens = lengths(mix["prompt_tokens"], n, rng, block)
    olens = lengths(mix["output_tokens"], n, rng, block)
    reqs = []
    for i in range(n):
        prng = random.Random(f"prompt/{seed}/{i}")
        reqs.append({"index": i, "due": dues[i], "max_tokens": olens[i],
                     "prompt": [prng.randrange(1, vocab_size)
                                for _ in range(plens[i])]})
    return reqs


TRACE_LEAD_S = 0.5     # the trace starts this long before its request is due


def trace_start_s(schedule, mix, ramp_s, seconds, trace_s):
    """Where a traced run lays its ``trace_s`` seconds of device trace:
    ``(start, request)``, the start in seconds from the generator's start.

    A closed loop keeps every slot busy, so it traces the middle of the
    window and ``request`` is None. An open loop below its knee stands empty
    between arrivals, and the middle may hold no request at all; there the
    trace is laid on a request of the schedule: of those due at least 2 s
    into the window and at least 10 s before its end (``stop_trace`` takes
    seconds, and should not run far past the window), the one with most
    ``max_tokens``, the earliest of equals; the trace starts ``TRACE_LEAD_S``
    before it is due, so that its admission and prefill are inside, and
    never before the window does. With none due there, the same among those
    due anywhere a whole trace still fits; with none at all, the middle.

    Only ``due`` and ``max_tokens`` are read, nothing of how fast the system
    is: a parent and a change trace the same request on the same seed."""
    # the window's two ends halved, as the harness always wrote it: the
    # closed-loop cells' traced seconds do not move by a rounding
    middle = (ramp_s + (ramp_s + seconds)) / 2.0 - trace_s / 2.0
    if mix["loop"] != "open":
        return middle, None
    for lo, hi in ((ramp_s + 2.0, ramp_s + seconds - 10.0),
                   (ramp_s, ramp_s + seconds - trace_s)):
        due = [r for r in schedule if lo <= r["due"] <= hi]
        if due:
            req = max(due, key=lambda r: (r["max_tokens"], -r["due"]))
            return max(req["due"] - TRACE_LEAD_S, ramp_s), req
    return middle, None


def check_prompts(spec, seed, vocab_size):
    """The few prompts whose served tokens the reference judges."""
    rng = random.Random(f"check/{seed}")
    return [[rng.randrange(1, vocab_size) for _ in range(n)]
            for n in lengths(spec["prompt_tokens"], spec["count"], rng)]


def train_sample(seed, index, seq_len, vocab_size):
    """One training sequence: uniform random token ids (numpy, so only the
    training child calls this)."""
    import numpy as np
    rs = np.random.RandomState((seed * 1000003 + index) % (2 ** 31 - 1))
    return rs.randint(0, vocab_size, (seq_len,)).astype(np.int32)


def train_check_tokens(seed, rows, seq_len, count):
    """The ``count`` (row, position) pairs at which the reference judges a
    training step's forward pass: rows in turn, one position from each of
    ``count`` equal stretches of the sequence, so short and long contexts
    are both judged. Position s predicts token s + 1, so s < seq_len - 1."""
    rng = random.Random(f"train-check/{seed}")
    edges = [i * (seq_len - 1) // count for i in range(count + 1)]
    return [(i % rows, rng.randrange(edges[i], max(edges[i + 1],
                                                   edges[i] + 1)))
            for i in range(count)]
