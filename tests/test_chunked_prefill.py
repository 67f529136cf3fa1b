"""Chunked prefill on the paged serving path (serving/engine.py
``prefill_chunk``, README "Chunked prefill"): long cold prompts prefill
``prefill_chunk`` tokens per engine step, interleaved with the fused
decode tick, instead of monopolizing a step.

The load-bearing properties:

- **Transparency**: chunked token streams are byte-identical to the
  unchunked engine — greedy AND seeded-sampled, cold and prefix-cache
  hit admissions alike. Only the FINAL chunk samples (and advances the
  PRNG), so the key walk is exactly the one-shot prefill's.
- **Interleaving**: decode slots keep emitting a token on every step a
  chunk runs — the TTFT win chunking exists for.
- **Compile discipline**: ``decode_compilations() == 2`` (one program a
  packed size: chunk-carrying steps and decode-only steps) and a CLOSED
  chunk-prefill compile set (full chunks share the ``prefill_chunk``
  bucket; remainders ride the pow2 grid) under varied prompt lengths
  and a mixed hit/miss/cancel/divergence matrix.
- **Lifecycle**: cancellation/timeout mid-chunk restores ``num_free``
  exactly — the partial block chain is freed (or donated to the trie,
  which later resumes the SAME prompt at the donated offset).
- **Generated-token trie extension**: retirement donates full
  *generated* blocks too, so a multi-turn resubmission of turn N's
  assistant text hits turn N's own blocks.
"""
import time

import numpy as np
import pytest

from paddle_tpu.serving import FIFOScheduler, GenerationRequest

import serving_support
from serving_support import BS, clone as _clone, prompt as _prompt
from test_metrics_prom import parse_prometheus


@pytest.fixture(scope="module")
def model():
    return serving_support.model("llama", seed=21)  # GQA: nkv=2 < nh=4


def _engine(model, **kw):
    """The shared helper under fixed-cap chunk pacing: the step-count and
    offset pins below assume exactly CHUNK tokens per grant; the
    headroom-adaptive budget is wall-clock-fed (nondeterministic on a
    shared box) and is pinned separately in test_ragged_step.py with an
    injected clock."""
    kw.setdefault("headroom_mult", None)
    return serving_support.engine(model, **kw)


def _req(ps, n=40, **kw):
    kw.setdefault("max_new_tokens", 6)
    return GenerationRequest(prompt=_prompt(ps, n), **kw)


def _run(model, reqs, **kw):
    eng = _engine(model, **kw)
    outs = eng.generate([_clone(r) for r in reqs])
    return [o.tolist() for o in outs], eng


class TestTransparency:
    @pytest.mark.slow  # 8 s transparency duplicate: test_chunked_equals_
    # unchunked_with_prefix_hits below is the stricter default rep (870s cap)
    def test_chunked_equals_unchunked_greedy_and_sampled(self, model):
        """The acceptance pin: varied prompt lengths (sub-chunk,
        multi-chunk, non-block-multiple), greedy and seeded-sampled,
        stream the exact unchunked tokens, with one decode program a
        packed size (chunk-carrying steps, decode-only steps)."""
        reqs = [_req(1, n=40), _req(2, n=61), _req(3, n=12),
                _req(4, n=53, temperature=0.9, top_k=5, seed=123),
                _req(5, n=33, temperature=0.7, top_k=3, seed=9)]
        want, _ = _run(model, reqs, prefill_chunk=None)
        got, eng = _run(model, reqs)
        assert got == want
        assert eng.stats["prefill_chunks"] >= 8  # 40,61,53,33 all chunked
        assert eng.decode_compilations() == 2

    def test_chunked_equals_unchunked_with_prefix_hits(self, model):
        """Hit admissions: the installed chain counts toward the resume
        offset (zero-copy) and streams stay byte-identical to both the
        unchunked-hit and the cold engines."""
        sysp = _prompt(50, 32)
        reqs = [GenerationRequest(
            prompt=np.concatenate([sysp, _prompt(51 + i, 24)]),
            max_new_tokens=5,
            **({"temperature": 0.8, "top_k": 4, "seed": 3} if i == 2
               else {})) for i in range(3)]
        cold, _ = _run(model, reqs, prefix_cache=False, prefill_chunk=None)
        unchunked, _ = _run(model, reqs, prefix_cache=True,
                            prefill_chunk=None)
        chunked, eng = _run(model, reqs, prefix_cache=True)
        assert chunked == unchunked == cold
        assert eng.prefix_cache.stats["hits"] >= 1
        # the hit's covered tokens were never re-prefilled
        assert eng.stats["prefill_tokens_saved"] > 0

    def test_decode_slots_keep_emitting_while_chunk_runs(self, model):
        """The TTFT property itself: on every step that advances a
        pending prefill chunk, the live decode slot still emits a
        token — no decode batch ever waits behind the long prompt.
        (A step accepts the program dispatched by the step before it, so
        the long prompt runs one step after its final chunk went out.)"""
        eng = _engine(model)
        short = eng.submit(_req(10, n=8, max_new_tokens=40))
        eng.step()                      # short admitted + first token
        assert short.status == "running"
        longy = eng.submit(_req(11, n=80, max_new_tokens=4))
        n_steps = 0
        while longy.status != "running":
            before = len(short.tokens)
            chunks0 = eng.stats["prefill_chunks"]
            eng.step()
            # the first of these steps dispatches chunk 0 and accepts a
            # decode-only program; each later one accepts a chunk
            assert eng.stats["prefill_chunks"] == chunks0 + (n_steps > 0)
            assert len(short.tokens) == before + 1  # decode kept going
            n_steps += 1
        assert n_steps == 5 + 1         # ceil(80 / 16) chunks
        assert eng.stats["prefill_chunks"] == 5
        # the long prompt's stream is still the solo/unchunked one
        while eng.has_work():
            eng.step()
        want, _ = _run(model, [_req(11, n=80, max_new_tokens=4)],
                       prefill_chunk=None)
        assert longy.tokens == want[0]

    def test_prefilling_status_walks_and_offsets_block_aligned(self, model):
        eng = _engine(model)
        seq = eng.submit(_req(12, n=50, max_new_tokens=2))
        assert seq.status == "queued"
        offs = []
        eng.step()
        while seq.status == "prefilling":
            offs.append(seq.prefilled)
            eng.step()
        assert seq.status in ("running", "finished")
        # block-aligned resume offsets, advanced as each chunk is
        # dispatched; 50 while the final chunk is in flight
        assert offs == [16, 32, 48, 50]
        assert seq.prefilled == 50


class TestCompileDiscipline:
    def test_closed_compile_set_under_mixed_matrix(self, model):
        """The acceptance pin: a mixed hit/miss/cancel/divergence
        traffic matrix over varied prompt lengths leaves
        decode_compilations() == 2 (both packed sizes reached, and no
        third program), and once the (group, bucket) grid
        is warm a repeat wave adds ZERO prefill/suffix traces — chunk
        calls all land in the prefill_chunk (or remainder pow2)
        buckets."""
        eng = _engine(model, prefix_cache=True, num_slots=2)
        sysp = _prompt(60, 32)

        def wave(cancel_at=None):
            reqs = [GenerationRequest(prompt=np.concatenate(
                        [sysp, _prompt(61 + i, 9 + 8 * i)]),
                        max_new_tokens=4) for i in range(3)]
            reqs.append(_req(65, n=43, temperature=0.8, top_k=6, seed=2))
            seqs = [eng.submit(r) for r in reqs]
            steps = 0
            while eng.has_work():
                eng.step()
                steps += 1
                if cancel_at is not None and steps == cancel_at:
                    victim = next((s for s in seqs
                                   if s.status == "prefilling"), None)
                    if victim is not None:
                        eng.cancel(victim)
            return [s.tokens for s in seqs]

        first = wave()
        wave(cancel_at=2)               # cancel mid-chunk in the mix
        assert eng.decode_compilations() == 2
        prefill0 = eng.prefill_compilations()
        third = wave()
        assert third == first           # steady-state determinism
        assert eng.decode_compilations() == 2
        assert eng.prefill_compilations() == prefill0  # zero new traces

    def test_chunk_bucket_is_shared_across_prompt_lengths(self, model):
        """Prompts of many lengths chunk through ONE full-chunk bucket:
        the suffix compile count stays bounded by the pow2 grid, not by
        the number of distinct prompt lengths."""
        eng = _engine(model, max_seq_len=96)
        built = eng.prefill_compilations()     # by the module's other tests
        for i, n in enumerate((33, 41, 49, 57, 65, 73, 81, 89)):
            eng.generate([_req(70 + i, n=n, max_new_tokens=2)])
        # full chunks: one (G=1, 16) trace; remainders: pow2 buckets
        # {8, 16} at G=1 -> <= 3 suffix traces total for 8 lengths
        assert eng.prefill_compilations() - built <= 3
        assert eng.decode_compilations() == 2


class TestLifecycle:
    def test_cancel_mid_chunk_restores_num_free_exactly(self, model):
        """No trie: cancelling a half-prefilled prompt returns every
        pool block and the slot; the engine is byte-for-byte reusable."""
        eng = _engine(model)
        pool = eng.cache.pool
        blocks0, slots0 = pool.num_free, eng.cache.num_free
        bystander = eng.submit(_req(20, n=8, max_new_tokens=20))
        victim = eng.submit(_req(21, n=70, max_new_tokens=4))
        want = None
        for _ in range(3):
            eng.step()
        assert victim.status == "prefilling"
        assert 0 < victim.prefilled < 70
        assert eng.cancel(victim) is True
        assert victim.finish_reason == "cancelled"
        assert victim.tokens == []
        assert eng.cache.num_free == slots0 - 1   # bystander still live
        while eng.has_work():
            eng.step()
        assert pool.num_free == blocks0
        assert eng.cache.num_free == slots0
        want, _ = _run(model, [_req(20, n=8, max_new_tokens=20)],
                       prefill_chunk=None)
        assert bystander.tokens == want[0]        # bystander untouched

    def test_timeout_mid_chunk_frees_partial_chain(self, model):
        eng = _engine(model)
        pool = eng.cache.pool
        blocks0 = pool.num_free
        seq = eng.submit(_req(22, n=70, max_new_tokens=4,
                              timeout_s=60.0))
        eng.step()
        assert seq.status == "prefilling"
        # force expiry deterministically (a tiny wall-clock timeout_s
        # can fire while still queued on a loaded box): the sweep reads
        # the absolute deadline, so backdating it IS the timeout
        seq.deadline = time.monotonic() - 1.0
        eng.step()                       # deadline sweep fires
        assert seq.finish_reason == "timeout"
        assert seq.tokens == []
        assert eng.stats["timeouts"] == 1
        assert pool.num_free == blocks0
        assert eng.cache.num_free == eng.num_slots

    def test_cancelled_chunk_donates_partial_chain_to_trie(self, model):
        """With the prefix cache on, a mid-prefill cancel DONATES the
        block-aligned partial chain — resubmitting the same prompt
        resumes from the donated offset instead of starting cold."""
        eng = _engine(model, prefix_cache=True)
        seq = eng.submit(_req(23, n=70, max_new_tokens=4))
        eng.step()
        eng.step()
        assert seq.prefilled == 32
        eng.cancel(seq)
        matched = eng.prefix_cache.lookup(_prompt(23, 70), record=False)
        assert len(matched) == 4         # 32 donated rows = 4 blocks
        # resume: same prompt now hit-installs the donated chain and
        # still streams the unchunked tokens
        want, _ = _run(model, [_req(23, n=70, max_new_tokens=4)],
                       prefill_chunk=None)
        out = eng.generate([_req(23, n=70, max_new_tokens=4)])[0]
        assert out.tolist() == want[0]
        assert eng.stats["prefill_tokens_saved"] >= 32


class TestGeneratedTokenDonation:
    def test_multi_turn_resubmission_hits_generated_blocks(self, model):
        """Turn N+1's prompt embeds turn N's assistant output:
        retirement donated the generated full blocks, so the lookup
        covers past the original prompt and the stream still matches a
        cold engine byte for byte."""
        eng = _engine(model, prefix_cache=True)
        turn1 = _req(30, n=40, max_new_tokens=10)
        out1 = eng.generate([_clone(turn1)])[0]
        history = np.concatenate([turn1.prompt, out1.ids])
        # generated rows: all but the last sampled token are in KV
        matched = eng.prefix_cache.lookup(
            np.concatenate([history, [1, 2, 3]]), record=False)
        assert len(matched) * BS >= 48   # covers into the generated tail
        assert eng.prefix_cache.stats["donated_blocks"] >= 6
        turn2 = GenerationRequest(
            prompt=np.concatenate([history, [1, 2, 3]]).astype(np.int32),
            max_new_tokens=6)
        want, _ = _run(model, [turn2], prefix_cache=False,
                       prefill_chunk=None)
        got = eng.generate([_clone(turn2)])[0]
        assert got.tolist() == want[0]
        assert eng.prefix_cache.stats["hit_tokens"] >= 48

    def test_last_token_kv_never_donated(self, model):
        """The final sampled token's KV is never written (its append
        would belong to the decode tick that never ran) — donation must
        cap at the written rows, or a later hit would read garbage."""
        eng = _engine(model, prefix_cache=True, max_seq_len=96)
        # 39 prompt + 9 generated = 48 content rows, 47 written: block 5
        # (rows 40..47) must NOT be donated even though content fills it
        r = _req(31, n=39, max_new_tokens=9)
        out = eng.generate([_clone(r)])[0]
        full = np.concatenate([r.prompt, out.ids])
        matched = eng.prefix_cache.lookup(
            np.concatenate([full, [7]]), record=False)
        assert len(matched) == 5         # 47 written rows -> 5 blocks


class TestSchedulerPolicy:
    def test_prefill_plan_budgets_fifo_block_aligned(self):
        class S:
            def __init__(self, plen, done):
                # work_len is what the plan budgets (== prompt_len for
                # anything not restored for recovery-by-recompute)
                self.work_len, self.prefilled = plen, done
        sched = FIFOScheduler()
        a, b = S(100, 64), S(50, 0)
        sched.enter_prefill(a)
        sched.enter_prefill(b)
        # head's final 36 tokens fit; leftover 28 block-aligns to 24
        assert sched.prefill_plan(64, align=8) == [(a, 36), (b, 24)]
        # a non-final cut is rounded DOWN to a block boundary
        a.prefilled = 0
        assert sched.prefill_plan(20, align=8) == [(a, 16)]
        # sub-block leftover stops the plan instead of splitting
        assert sched.prefill_plan(4, align=8) == []
        sched.leave_prefill(a)
        assert sched.prefill_plan(64, align=8) == [(b, 50)]
        assert sched.leave_prefill(a) is False   # idempotent

    def test_pending_prefill_forces_single_stepping(self):
        class S:
            def __init__(self, remaining):
                self.remaining = remaining
        sched = FIFOScheduler(decode_chunk=8)
        assert sched.choose_num_steps([S(20), S(20)]) == 8
        sched.enter_prefill(object())
        assert sched.choose_num_steps([S(20), S(20)]) == 1
        sched.prefilling.clear()
        assert sched.choose_num_steps([S(20), S(20)]) == 8


class TestConfigSurface:
    def test_chunk_rounds_up_to_block_multiple(self, model):
        eng = _engine(model, prefill_chunk=17)
        assert eng._chunk == 24          # next multiple of BS=8
        assert eng.prefill_chunk == 24   # the public effective value
        with pytest.raises(ValueError, match="prefill_chunk"):
            _engine(model, prefill_chunk=-1)
        assert _engine(model, prefill_chunk=0).prefill_chunk == 0
        assert _engine(model, prefill_chunk=None)._chunk is None

    def test_metrics_surface_strict_parsed(self, model):
        """serving_prefill_chunks_total counts chunk work on /metrics
        and serving_ttft_seconds uses the TTFT bucket ladder — all
        valid under the strict v0.0.4 parser."""
        from paddle_tpu.profiler.metrics import TTFT_BUCKETS
        from paddle_tpu.serving.server import ServingGateway
        eng = _engine(model)
        gw = ServingGateway(eng, start=False)   # no driver thread needed
        eng.generate([_req(42, n=50, max_new_tokens=2)])
        gw._m_ttft.observe(0.0007)   # engine-direct runs bypass the
        # gateway's submit path; one observation materializes the series
        fams = parse_prometheus(gw.registry.render())
        name = "serving_prefill_chunks_total"
        assert fams[name]["type"] == "counter"
        assert fams[name]["samples"][(name, ())] == \
            eng.stats["prefill_chunks"] >= 3
        # the TTFT histogram exposes the dedicated ladder
        le = [k for k in fams["serving_ttft_seconds"]["samples"]
              if k[0] == "serving_ttft_seconds_bucket"]
        bounds = {lbl[1] for _, lbls in le for lbl in lbls
                  if lbl[0] == "le"}
        assert "0.0005" in bounds          # sub-ms low end
        assert "30" in bounds              # _fmt_value renders 30.0 -> 30
        assert len(bounds) == len(TTFT_BUCKETS) + 1  # ladder + +Inf
