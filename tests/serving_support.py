"""Where a serving test gets its model, its engine and its compiled programs.

The rule (ROADMAP D6): **a step program is traced, lowered and compiled once
in a process, and no test's verdict waits on a compile.** Lowering a step
program with the interpreted kernels in it costs seconds and nothing caches it
but the jitted callable itself, so the callable is kept:

- :func:`model` holds one model per (architecture, tiny config, seed) for the
  worker process, not per module: two files that ask for the same model share
  it, and with it everything the package hangs on a model (the quantised
  parameters, the placed TP parameters, ``serve()``'s and a fleet's program
  caches).
- :func:`programs` holds one program cache per model and argument geometry. The
  engine's keys carry what chooses a program (slots, packed size, variant tags)
  but not what shapes its arguments (the pool's blocks, the block tables'
  width), and one jitted callable traced at two shapes counts two compilations:
  so engines whose arguments differ in shape get dicts of their own, and
  ``decode_compilations()`` / ``prefill_compilations()`` read on the shared
  cache what they read on a fresh one.
- :func:`engine` builds a ``ContinuousBatchingEngine`` at the suite's default
  geometry on that cache. A file passes its own geometry only where the test is
  about that geometry.

A test that needs programs nobody else may run (a function patched in before
the trace, a recorder inside the program) passes ``jit_cache=`` itself and
says why.
"""
import functools
import importlib
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ContinuousBatchingEngine, GenerationRequest
from paddle_tpu.serving.prefix_cache import PrefixCache

BS = 8       # KV block size
CHUNK = 16   # prefill chunk: two blocks
SLOTS = 2
S_MAX = 96

#: every switch that runs a program other than the default engine's two, a
#: case each: a model with a state store or a ring raises for each by name
OTHER_SWITCHES = (
    dict(quantize_weights=True), dict(tp=2), dict(decode_ticks=4),
    dict(spec_decode=True), dict(decode_chunk=4), dict(prefix_cache=True),
    dict(kv_dtype="int8"),
    pytest.param(dict(kv_dtype="fp8"), id="kv_dtype-fp8"),
    pytest.param(dict(quantize_weights=True, quantize_activations=True),
                 id="quantize_activations"))

_MODELS = {}


#: architecture: (module under ``paddle_tpu.models``, model class, tiny config)
_ARCH = {
    "llama": ("llama", "LlamaForCausalLM", "llama_tiny"),
    "olmoe": ("olmoe", "OlmoeForCausalLM", "olmoe_tiny"),
    "deepseek_v2": ("deepseek_v2", "DeepseekV2ForCausalLM",
                    "deepseek_v2_tiny"),
    "olmo_hybrid": ("olmo_hybrid", "OlmoHybridForCausalLM",
                    "olmo_hybrid_tiny"),
    "phi4_flash": ("phi4_flash", "Phi4FlashForCausalLM", "phi4_flash_tiny"),
    "glm_moe_dsa": ("glm_moe_dsa", "GlmMoeDsaForCausalLM",
                    "glm_moe_dsa_tiny"),
    "nemotron_h": ("nemotron_h", "NemotronHForCausalLM", "nemotron_h_tiny"),
    "jamba": ("jamba", "JambaForCausalLM", "jamba_tiny"),
    "qwen3_next": ("qwen3_next", "Qwen3NextForCausalLM", "qwen3_next_tiny"),
    "mimo_v2_flash": ("mimo_v2_flash", "MiMoV2FlashForCausalLM",
                      "mimo_v2_flash_tiny"),
}


def fresh_model(arch="llama", seed=33, **config):
    """A model nobody else holds, for a test that writes on its model (a
    routing record, a popped cache). Same weights as :func:`model` gives."""
    module, cls, tiny = _ARCH[arch]
    module = importlib.import_module("paddle_tpu.models." + module)
    paddle.seed(seed)
    return getattr(module, cls)(getattr(module, tiny)(**config))


def model(arch="llama", seed=33, **config):
    """The process's one model of this architecture, tiny configuration
    (``config`` overrides the architecture's ``*_tiny()``) and seed. Its
    weights are a function of the three, so a file reads the same model
    whichever file built it."""
    # (a list, such as a layer pattern, keys as a tuple)
    key = (arch, seed, tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in config.items())))
    if key not in _MODELS:
        _MODELS[key] = fresh_model(arch, seed, **config)
    return _MODELS[key]


def programs(model, max_seq_len=None, prefix_block_size=None,
             prefix_cache=False, prefix_blocks=None, **_):
    """The model's program cache for engines whose arguments have these
    shapes: the block tables' width (``max_seq_len`` in blocks) and the
    pool's block count (the live grid plus a trie's budget). Takes an
    engine's keyword arguments (``None``: the engine's own default) and
    ignores those the engine's own keys carry."""
    if isinstance(prefix_cache, PrefixCache):
        trie = ("shared", prefix_cache.pool.num_blocks)
    else:
        trie = bool(prefix_cache) and (prefix_blocks or "default")
    return model.__dict__.setdefault("_test_programs", {}).setdefault(
        (max_seq_len, prefix_block_size, trie), {})


def engine_as_given(model, **kw):
    """A ``ContinuousBatchingEngine`` with the ENGINE's own defaults for
    what ``kw`` leaves out, on the model's shared programs: for a file whose
    tests are about those defaults (what ``serve()`` builds)."""
    if kw.get("jit_cache") is None:
        kw["jit_cache"] = programs(model, **kw)
    return ContinuousBatchingEngine(model, **kw)


def engine(model, **kw):
    """A ``ContinuousBatchingEngine`` at the suite's geometry (2 slots, 96
    positions, blocks of 8, chunks of 16, one step a call) on the model's
    shared programs."""
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("max_seq_len", S_MAX)
    kw.setdefault("decode_chunk", 1)
    kw.setdefault("prefix_block_size", BS)
    kw.setdefault("prefill_chunk", CHUNK)
    return engine_as_given(model, **kw)


def watch_prefill_programs(eng):
    """Count, as ``eng.prefill_programs_asked``, the whole-prompt prefill
    programs ``eng`` asks for from here on. ``prefill_compilations() == 0``
    says of a fresh cache that a chunked prompt built no whole-prompt
    program; this says it of the engine, so it holds on a shared cache
    whichever test ran first."""
    eng.prefill_programs_asked = 0
    real = eng._prefill_fn

    def asked():
        eng.prefill_programs_asked += 1
        return real()

    eng._prefill_fn = asked
    return eng


def model_drafter(model):
    """``model`` drafting for itself (the always-accept oracle) on the
    model's one draft program."""
    from paddle_tpu.serving import ModelDrafter
    return ModelDrafter(model, jit_cache=programs(model))


def prompt(seed, n=12, low=0):
    """``n`` token ids drawn from ``seed``."""
    return np.random.RandomState(seed).randint(low, 256, (n,)).astype(np.int32)


def token_list(n, seed=0):
    """``prompt`` as the HTTP surface takes it: a list, no token 0."""
    return prompt(seed, n, low=1).tolist()


def clone(r):
    """A fresh request with ``r``'s content: a request is consumed by the
    engine that serves it."""
    return GenerationRequest(prompt=r.prompt,
                             max_new_tokens=r.max_new_tokens,
                             temperature=r.temperature, top_k=r.top_k,
                             seed=r.seed, eos_token_id=r.eos_token_id)


def mixed_reqs(sampled=False, n_reqs=4, max_new=8):
    """Mixed trace: two shared system prompts with unique tails (trie
    traffic) and repetition, so that the n-gram drafter has something to
    hit."""
    sys_p = [prompt(100 + i, 24) for i in range(2)]
    out = []
    for i in range(n_reqs):
        tail = np.tile(prompt(i, 4), 3).astype(np.int32)
        kw = dict(max_new_tokens=max_new)
        if sampled:
            kw.update(temperature=0.8, top_k=20, seed=500 + i)
        out.append(GenerationRequest(
            prompt=np.concatenate([sys_p[i % 2], tail]), **kw))
    return out


def run(eng, reqs):
    """The streams of ``reqs`` (cloned) through ``eng``, as lists."""
    return [list(o) for o in eng.generate([clone(r) for r in reqs])]


def drain(eng, between=None):
    """Step ``eng`` until it has no work, calling ``between`` after each."""
    while eng.has_work():
        eng.step()
        if between is not None:
            between()


def match_fraction(a, b):
    """Mean matched-prefix fraction across paired streams: the measured
    (not assumed) divergence statistic of the quantised paths."""
    fracs = []
    for x, y in zip(a, b):
        m = 0
        for t, u in zip(x, y):
            if t != u:
                break
            m += 1
        fracs.append(m / max(len(x), 1))
    return sum(fracs) / len(fracs)


def compiled_once(fn, static=("block_q", "pages", "window")):
    """A kernel entry point ``fn`` as ONE jitted program a set of static
    keywords (and, by ``jax.jit``, a set of shapes): called eagerly, every
    ``jnp`` op around the kernel (the work list alone is dozens) is a
    program of its own to compile, and every call lowers the kernel anew
    (ISSUE 43). The rule's form for a kernel-level file."""
    @functools.lru_cache(maxsize=None)
    def program(static_kw):
        return jax.jit(functools.partial(fn, **dict(static_kw)))

    def call(*args, **kw):
        fixed = tuple(sorted((k, v) for k, v in kw.items()
                             if k in static and v is not None))
        return program(fixed)(*args, **{
            k: v for k, v in kw.items() if k not in static})

    return call


def wait_until(done, what="the condition", hang_s=600.0):
    """Wait on progress, not on the clock: poll ``done()`` until it holds.
    What is waited for may sit behind a compile whose length is the
    machine's load, so the wall clock is kept only as a guard against a
    hang."""
    guard = time.monotonic() + hang_s
    while not done():
        assert time.monotonic() < guard, f"hung waiting for {what}"
        time.sleep(0.005)


class LogitsRecorder:
    """Every program's logits (``decode._head_logits``), in dispatch order,
    and for every token a sequence is given the row it was sampled from, for
    a module whose engines have ``slots`` slots and chunks of ``chunk``
    (a step program's logits are ``[slots, V]``, a whole-prompt group's are
    not: the tests that use it serve groups of one). Patched in through
    ``monkeypatch`` for as long as the programs traced with it are kept."""

    def __init__(self, monkeypatch, slots, chunk):
        from paddle_tpu.serving import decode as decode_mod
        self.records, self.rows = [], {}
        self.slots, self.chunk = slots, chunk
        real = decode_mod._head_logits
        while hasattr(real, "recorded"):    # never a recorder in a recorder
            real = real.recorded

        def recording(last_h, head):
            logits = real(last_h, head)
            jax.debug.callback(lambda x: self.records.append(np.asarray(x)),
                               logits, ordered=True)
            return logits

        recording.recorded = real
        monkeypatch.setattr(decode_mod, "_head_logits", recording)

    def clear(self):
        del self.records[:]
        self.rows.clear()
        return self

    def watch(self, eng):
        def on_token(seq, _tok):
            jax.effects_barrier()
            rows = self.rows.setdefault(seq.request_id, [])
            whole = seq.work_len <= self.chunk
            if len(seq.tokens) == 1 and whole:
                group = [r for r in self.records
                         if r.shape[0] != self.slots][-1]
                rows.append(group[0])    # groups of one in these tests
                return
            steps = [r for r in self.records if r.shape[0] == self.slots]
            rows.append(steps[-2 if eng._inflight is not None
                              else -1][seq.slot])

        eng.on_token = on_token


def reference_logits(ref, model, ids, at, width, config=None):
    """A plain reference's (``benchmark/reference_*.py``) logits ``[len(at),
    V]`` at positions ``at`` of ONE sequence, read at one padded ``width``
    (every layer is causal, so what follows a position is not seen, and the
    reference compiles once)."""
    row = np.zeros((1, width), np.int32)
    row[0, :len(ids)] = ids
    return np.asarray(ref.logits_at(
        ref.weights_of(model), ref.hyper_of(config or model.config), row,
        np.asarray([at], np.int32)))[0]


def deviation(ref, model, seq, rows, width):
    """max |engine logits - reference logits| over ``seq``'s generated
    positions, as a share of the reference's largest |logit|."""
    prompt, tokens = list(seq.prompt), list(seq.tokens)
    want = reference_logits(ref, model, prompt + tokens, [
        len(prompt) - 1 + k for k in range(len(tokens))], width)
    assert len(rows) == len(tokens)
    return float(np.abs(np.stack(rows) - want).max() / np.abs(want).max())
