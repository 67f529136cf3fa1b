"""The experts the step programs picked, kept for the newest sequences.

A routed-FFN model may bring a :class:`RoutingRecord` (``model.routing_record``).
The engine then builds its two default programs with their picks as one more
output, ``[L_routed, rows, top_k]`` int32 by the router's ids, and notes after
every dispatch which rows of it belong to which sequence at which positions.
The arrays stay on the device and nothing is fetched until somebody asks
(:meth:`RoutingRecord.lookup`), so a step pays one small output and a short
host list. The record is bounded by bytes: the oldest programs' picks go first,
and a sequence whose first rows have gone is no longer found.

What it is for: replaying or judging a served sequence's routing. With only a
share of the experts held, a pick that lands elsewhere leaves no trace in the
output, so the routing the TIMED programs made can be checked against a
reference only if they say what it was (``benchmark/reference_deepseek_v2.py``
teacher-forces these picks and then judges logits and picks alike).
"""
from __future__ import annotations

from collections import deque

import numpy as np


class RoutingRecord:
    def __init__(self, max_bytes=64 << 20):
        self.max_bytes = int(max_bytes)
        self._calls = deque()       # (picks on the device, [(seq, row0, n, pos0)])
        self._bytes = 0

    def note(self, picks, rows):
        """One program call: ``picks [L, rows, top_k]`` (a device array, not
        fetched here) and ``rows``, its live spans as ``(sequence, first row,
        rows, first position)``."""
        if not rows:
            return
        self._calls.append((picks, rows))
        self._bytes += picks.nbytes
        while self._bytes > self.max_bytes and len(self._calls) > 1:
            self._bytes -= self._calls.popleft()[0].nbytes

    def lookup(self, tokens):
        """The picks of the newest recorded sequence whose content (prompt,
        then what it generated) is a prefix of ``tokens``: ``[L, len(tokens),
        top_k]`` int32, -1 at positions no program ran (a sequence's last
        sampled token is never fed back, and ``tokens`` may be padded).
        None when no such sequence is held from its first position on."""
        tokens = np.asarray(tokens).reshape(-1)
        calls = list(self._calls)       # the engine's thread may append
        seqs = {id(seq): seq for _, rows in calls for seq, *_ in rows}
        for seq in reversed(seqs.values()):
            own = np.concatenate([seq.prompt,
                                  np.asarray(seq.tokens, np.int32)])
            if own.size > tokens.size or not np.array_equal(
                    own, tokens[:own.size]):
                continue
            out = None
            for picks, rows in calls:
                mine = [r for r in rows if r[0] is seq]
                if not mine:
                    continue
                p = np.asarray(picks)           # the fetch
                if out is None:
                    out = np.full((p.shape[0], tokens.size, p.shape[-1]), -1,
                                  np.int32)
                for _, row0, n, pos0 in mine:
                    n = min(n, tokens.size - pos0)
                    out[:, pos0:pos0 + n] = p[:, row0:row0 + n]
            # every position a program ran, from the first on
            if (out[0, :max(own.size - 1, 1), 0] >= 0).all():
                return out
        return None
