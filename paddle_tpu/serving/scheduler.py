"""Admission + step-size policy for the continuous-batching engine.

Orca-style iteration-level scheduling (PAPERS.md): the schedulable unit
is ONE decode step, so a request can join or leave the batch between any
two steps. The FIFO policy here does two jobs:

- **Admission**: pop queued sequences into free cache slots, oldest
  first, at the top of every engine step.
- **Prefill budgeting** (chunked prefill, README "Chunked prefill"):
  sequences whose uncovered prompt exceeds the engine's
  ``prefill_chunk`` enter a PREFILLING pipeline instead of running one
  monopolizing device call; :meth:`FIFOScheduler.prefill_plan` hands
  the engine at most ``budget`` prompt tokens of that backlog per step,
  oldest sequence first, with non-final chunk boundaries aligned to the
  KV block size — so every step still runs the fused decode tick for
  all live slots and no decode batch ever waits behind an entire long
  prompt.
- **Chunk fusion**: when nothing schedulable can change for a while
  (queue empty, no prefill backlog), tell the engine to run several
  decode steps in one fused device call (a ``lax.scan`` inside the
  jitted step) — the largest power of two fitting both ``decode_chunk``
  and every active sequence's remaining budget. This amortizes per-step
  host dispatch (one host-device round trip per call)
  without ever delaying an admission or a pending prefill chunk: any
  queued request or in-flight prefill forces single-stepping.
  The compiled step-size set is bounded at
  ``{1, 2, 4, …, decode_chunk}`` — log2(chunk)+1 programs.

EOS is the one event a fused chunk cannot see coming; a sequence that
hits EOS mid-chunk wastes the chunk's tail tokens (they are computed and
discarded). That is the standard multi-step-scheduling trade — bound it
by keeping ``decode_chunk`` modest, or set it to 1 to disable fusion.
"""
from __future__ import annotations

import itertools
from collections import deque


class FIFOScheduler:
    """First-come-first-served admission; fused chunks when safe."""

    def __init__(self, decode_chunk: int = 8):
        self.decode_chunk = max(int(decode_chunk), 1)
        self.queue = deque()
        self.prefilling = deque()   # admitted, mid-chunked-prefill (FIFO)
        self._plan_carry = 0        # sub-block budget owed to the plan head
        self._intake = itertools.count()  # FIFO seniority stamps

    def submit(self, seq):
        # the tick, not request_id, is the queue-order authority: a
        # sequence re-enqueued for recovery (engine.restore) keeps its
        # old id but arrives at its NEW queue position. Guarded setattr:
        # the scheduler stays duck-typed — unit tests submit plain
        # strings as queue entries, which reject attribute assignment;
        # only real Sequences ever reach the engine's admission unwind,
        # the stamp's one consumer.
        try:
            seq.queue_tick = next(self._intake)
        except AttributeError:
            pass
        self.queue.append(seq)

    @property
    def num_queued(self) -> int:
        return len(self.queue)

    @property
    def num_prefilling(self) -> int:
        return len(self.prefilling)

    # ------------------------------------------------- chunked prefill
    def enter_prefill(self, seq):
        """Admission handed ``seq`` a slot but its uncovered prompt is
        too long for one call: queue it for per-step chunking."""
        self.prefilling.append(seq)

    def leave_prefill(self, seq) -> bool:
        """Drop a sequence from the prefill pipeline (final chunk done,
        cancellation, or deadline expiry). Returns whether it was
        there. An emptied pipeline clears the plan carry eagerly: the
        engine stops calling :meth:`prefill_plan` while nothing is
        prefilling, so without this a sub-block grant banked against a
        cancelled prompt would leak into a LATER unrelated prompt's
        first chunk grant."""
        try:
            self.prefilling.remove(seq)
            if not self.prefilling:
                self._plan_carry = 0
            return True
        except ValueError:
            return False

    def prefill_plan(self, budget: int, align: int = 1, cap=None):
        """This step's chunk assignments: ``[(seq, n_tokens), ...]``,
        oldest PREFILLING sequence first, spending at most ``budget``
        prompt tokens total. A sequence's chunk is capped at its
        remaining uncovered prompt; a NON-final chunk end is rounded
        down to an ``align`` (KV block size) boundary so a partially
        prefilled prompt is always a whole-block prefix plus a host
        resume offset — leftover budget smaller than one block stops
        the plan rather than splitting a block. A grant too small to
        release even one block is not LOST, though: it carries to the
        next step's plan head (capped at one block), so a throttled
        per-step budget — e.g. the engine's headroom-adaptive grant
        under heavy decode load — still accumulates into whole-block
        progress instead of starving the pipeline behind one misaligned
        prompt. Sequences stay queued until :meth:`leave_prefill`; FIFO
        order is never reshuffled, so a long prompt cannot be starved
        by later arrivals. ``cap`` bounds the carried total: the
        engine's packed token buffer (and the chunk compile bucket) is
        sized for at most ``cap`` chunk tokens per step, so a banked
        carry must never push a full-cap grant past it — the carry only
        ever matters when the grant is throttled BELOW the cap."""
        budget = int(budget) + self._plan_carry
        if cap is not None:
            budget = min(budget, int(cap))
        self._plan_carry = 0
        plan = []
        for seq in self.prefilling:
            if budget <= 0:
                break
            # work_len, not prompt_len: a sequence restored for
            # recovery-by-recompute chunks through prompt + generated
            # content (engine.restore), a fresh one through its prompt
            remaining = seq.work_len - seq.prefilled
            n = min(budget, remaining)
            if n < remaining:           # non-final: block-align the cut
                n -= (seq.prefilled + n) % align
                if n <= 0:
                    break
            plan.append((seq, n))
            budget -= n
        if not plan and self.prefilling:
            # blocked head: bank the sub-block grant for the next step
            self._plan_carry = min(budget, int(align))
        return plan

    def spec_grants(self, wants, budget):
        """Per-slot DRAFT-token grants for a speculative verify step
        (README "Speculative decoding"): each running slot's verify
        span spends ``1 + grant`` positions of the step's packed token
        buffer, and the drafts share that buffer's headroom with the
        prefill-chunk grant — ``budget`` is whatever the chunk plan
        left. Greedy in the given order (the engine passes slot order:
        deterministic, stable across steps, so acceptance statistics
        are never reshuffled by admission churn); each grant is capped
        at its row's request. Returns a list aligned with ``wants``.
        """
        b = max(int(budget), 0)
        grants = []
        for want in wants:
            g = min(max(int(want), 0), b)
            grants.append(g)
            b -= g
        return grants

    def admissions(self, num_free: int, hit_len_fn=None):
        """Sequences to admit this step (pops up to ``num_free``).

        ``hit_len_fn(seq) -> int`` makes admission prefix-cache-aware:
        it is THE admission-time prefix lookup — the engine's hook
        records the hit, pins the matched chain (so nothing this step
        does can evict it before install), and returns the covered
        token count, which lands on ``seq.prefix_hit_tokens``. The
        admitted SET stays strictly the FIFO head (fairness — a hit
        never jumps a colder request's place in line); the batch is
        then ordered by ascending uncovered-suffix length, which keeps
        slot assignment and admission bookkeeping deterministic under
        any hit mix (device-call count is unchanged — the engine
        buckets either way). The sort is stable, so equal-suffix
        sequences keep FIFO order.
        """
        out = []
        while self.queue and len(out) < num_free:
            out.append(self.queue.popleft())
        if hit_len_fn is not None:
            for seq in out:
                seq.prefix_hit_tokens = int(hit_len_fn(seq))
            if len(out) > 1:
                # work_len, not prompt_len: the hit is measured against
                # the prefill work content, which for a restored
                # sequence includes its generated tokens
                out.sort(key=lambda s: s.work_len - s.prefix_hit_tokens)
        return out

    def remove(self, seq) -> bool:
        """Drop a still-queued sequence (cancellation / deadline expiry
        before admission). Returns whether it was found."""
        try:
            self.queue.remove(seq)
            return True
        except ValueError:
            return False

    def requeue_front(self, seq):
        """Put an admission-aborted sequence back at the queue HEAD
        (the engine's PoolExhausted repair path): it was popped this
        step but never installed, so restoring its FIFO position keeps
        admission order deterministic under preemption retries."""
        self.queue.appendleft(seq)

    def choose_decode_ticks(self, active_seqs, max_ticks: int) -> int:
        """How many on-device decode ticks the next MULTI-TICK step
        should fuse behind one host sync (engine ``decode_ticks > 1``,
        README "Multi-tick decode"). Unlike :meth:`choose_num_steps`,
        the program's tick count is a RUNTIME argument with per-slot
        EOS/budget retirement masked on device, so the choice is pure
        latency policy — no compile set to bound, no per-slot budget
        clamp needed:

        - **mixed traffic** (prefill backlog) clamps to 1: fusing n
          ticks would delay the next prompt chunk by n-1 ticks, the
          TTFT head-of-line blocking chunking exists to remove;
        - **waiting queue** shrinks n to the smallest active remaining
          budget: the earliest GUARANTEED retirement then lands exactly
          on a sync boundary, so a waiting request's admission is never
          pushed past a slot's known budget cut (an early EOS inside
          the block remains the standard multi-step trade — the device
          masks its cost, the host sees it at the sync);
        - otherwise n runs to the LARGEST active remaining budget
          (capped at ``max_ticks``): near-finished rows retire
          on-device mid-block instead of shrinking the block for
          everyone — the whole point of the alive mask.
        """
        if max_ticks <= 1 or self.prefilling or not active_seqs:
            return 1
        horizon = (min if self.queue else max)(
            s.remaining for s in active_seqs)
        return max(1, min(int(max_ticks), horizon))

    def choose_num_steps(self, active_seqs, budgets=None) -> int:
        """How many decode steps to fuse into the next device call:
        the largest power of two that fits both ``decode_chunk`` and
        every active sequence's remaining budget. Powers of two keep the
        compiled step-size set bounded (⊆ {1, 2, 4, …, decode_chunk})
        while letting a near-finished batch still fuse most of its tail
        instead of falling back to single-stepping. EOS-enabled
        sequences may finish early inside a chunk (tail discarded).
        In-flight chunked prefills also force single-stepping: fusing n
        decode ticks would delay the next prompt chunk by n-1 ticks,
        exactly the TTFT head-of-line blocking chunking exists to
        remove. ``budgets`` replaces each sequence's ``remaining`` where
        the engine knows better (a pipelined step: less the tokens
        already in flight)."""
        if self.decode_chunk == 1 or self.queue or self.prefilling \
                or not active_seqs:
            return 1
        m = min(budgets) if budgets is not None \
            else min(s.remaining for s in active_seqs)
        n = 1
        while n * 2 <= min(m, self.decode_chunk):
            n *= 2
        return n
