"""Window arithmetic on the client's record of a serving run: which events
count, what ``attempted`` and ``failed`` mean, percentiles. Pure functions
on hand-checkable lists (``tests/test_window.py``).

A request record is a dict: ``due`` (seconds on the client clock at which
it was due; for a closed loop, when it was sent), ``sent``, ``status``
(HTTP status or None), ``token_times`` (client clock at each streamed
token), ``finish`` (``"length"``, ``"error"``, ``"cut"``, ``"abandoned"``
or None while streaming), ``prompt_len``, ``max_tokens``.
"""
import math


def percentile(values, q):
    """Linear-interpolated percentile, q in [0, 100]; None if empty."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t, win):
    return win[0] <= t < win[1]


def attempted(records, win):
    """Requests due inside the window."""
    return [r for r in records if in_window(r["due"], win)]


def is_failed(r, win):
    """Refused (429 or any non-200), errored or cut by the server; or due in
    the window's first half and still without a first token at its end.
    A request still streaming at the end is abandoned, not failed."""
    if r["status"] is not None and r["status"] != 200:
        return True
    if r["finish"] in ("error", "cut"):
        return True
    first_half = r["due"] < (win[0] + win[1]) / 2.0
    no_first = not r["token_times"] or r["token_times"][0] >= win[1]
    return first_half and no_first


def failed(records, win):
    return [r for r in attempted(records, win) if is_failed(r, win)]


def ttfts_ms(records, win):
    """Due time to first streamed token, for first tokens that fall inside
    the window (whenever the request was due)."""
    return [(r["token_times"][0] - r["due"]) * 1e3 for r in records
            if r["token_times"] and in_window(r["token_times"][0], win)]


def gaps_ms(records, win):
    """Gaps between a request's consecutive streamed tokens, pooled, for
    gaps that end inside the window."""
    out = []
    for r in records:
        ts = r["token_times"]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:])
                   if in_window(b, win))
    return out


def tokens_in_window(records, win):
    return sum(1 for r in records for t in r["token_times"]
               if in_window(t, win))


def slo_attained_share(records, win, ttft_ms, gap_ms):
    """Share (%) of attempted requests whose TTFT and every gap seen by the
    window's end met the limits; a failed request misses."""
    att = attempted(records, win)
    if not att:
        return None
    ok = 0
    for r in att:
        if is_failed(r, win) or not r["token_times"]:
            continue
        ts = [t for t in r["token_times"] if t < win[1]]
        if not ts or (ts[0] - r["due"]) * 1e3 > ttft_ms:
            continue
        if all((b - a) * 1e3 <= gap_ms for a, b in zip(ts, ts[1:])):
            ok += 1
    return 100.0 * ok / len(att)
