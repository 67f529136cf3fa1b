"""Device-boundary cost observatory for the serving stack (README
"Cost attribution & /debug/profile").

PR 9's span tracer says *where wall-time goes*; this module says *what
crosses the host↔device boundary*: launches and bytes a decoded token
(MPK / PAPERS.md). A :class:`CostObservatory` wraps every
jitted program the engine hands out of its shared jit-cache in a
counting facade (:class:`_CountedProgram`) and records, per program
key:

- **dispatches** — exact execution counts (one per facade call; the
  facade IS the call, so the count cannot drift from reality);
- **host→device bytes** — the abstract byte size of every *host-
  resident* argument leaf (numpy arrays / scalars: exactly the leaves
  the runtime must copy to device at dispatch; device-resident
  ``jax.Array`` leaves — weights, the KV pool, carried key state —
  pass by reference and are correctly not charged);
- **device→host bytes** — the abstract byte size of the result leaves
  the engine actually fetches to host (declared per program via
  ``host_out`` at wrap time: the sampled tokens, the tick-0 keys of
  the unified step, the spec key walk — never the functionally-updated
  pool arrays, which are re-adopted device-side);
- **compile events** — ``_cache_size()`` deltas around each call, so a
  retrace is attributed to the program (and the step) that paid it;
- **wall EWMA / total** — per-call wall time on an injectable clock
  (the fault harness's ``VirtualClock`` slots in, making a chaos
  replay's exported accounting byte-identical).

All sizes come from abstract ``shape``/``dtype`` — **no device sync,
no ``.block_until_ready()``, no value reads** — so observing costs
nothing the program wasn't already paying.

Discipline mirrors the tracer's: the observatory is a host-side dict
updated by the single engine-driver thread; scrape-time readers
(``/metrics`` gauges, ``/debug/profile``) read ints under the GIL.
Disabled, every engine instrumentation site reduces to the one
``_co()`` attribute guard.
"""
from __future__ import annotations

import time

import jax
import numpy as np

#: every program kind the serving engine's jit-cache can hand out —
#: the fixed label set of ``serving_dispatches_total{program=...}``
#: (values scrape as 0 until a kind first runs).
PROGRAM_KINDS = ("prefill", "suffix", "psuffix", "decode", "pdecode",
                 "ragged", "mtick", "spec")


def _abstract(leaf):
    """Shape/dtype (and placement) of one argument leaf, detached from
    its buffer."""
    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=leaf.sharding)
    leaf = np.asarray(leaf)
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)


def _nbytes(leaf) -> int:
    """Abstract byte size of one pytree leaf — shape × itemsize, no
    device sync (works on jax Arrays, numpy arrays and scalars).

    Always the LOGICAL (global-shape) size: on a tensor-parallel
    engine a replicated host argument is physically broadcast to every
    mesh device and a sharded result leaf is materialized once per
    shard, but the boundary cost attributed here is the one logical
    copy — per-shard leaves must not be double-counted across the mesh
    (the cross-chip traffic TP adds is accounted SEPARATELY, as
    ``serving_collective_bytes_total{dtype}`` via
    :meth:`CostObservatory.record_collective`)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        n = 1
        for d in shape:
            n *= int(d)
        return n * np.dtype(dtype).itemsize
    try:
        return np.dtype(type(leaf)).itemsize
    except TypeError:
        return 8          # opaque python scalar: one word, by convention


def _label(key) -> str:
    """Stable per-program label from a jit-cache key tuple:
    ``("ragged", 8, 72, 1, "jnp")`` → ``"ragged[8,72,1,jnp]"``."""
    if len(key) == 1:
        return str(key[0])
    return f"{key[0]}[{','.join(str(k) for k in key[1:])}]"


# ------------------------------------------------------- jaxpr launch census
#: collective primitives the census bills as cross-chip wire operations
#: (the TP all-reduce pair and every schedule it can lower to)
COLLECTIVE_PRIMITIVES = ("psum", "all_to_all", "all_gather", "ppermute",
                         "reduce_scatter")


def _census_walk(jaxpr):
    """Count ``pallas_call`` and collective eqns in one (open) jaxpr,
    recursively. Returns ``(pallas, collectives, loop_bodies)`` where

    - ``scan`` bodies multiply by the static trip count (a scanned
      layer stack really launches its kernel once per layer);
    - ``while`` bodies count ONCE into the totals (the trip count is a
      runtime value) and additionally append their own PER-ITERATION
      census to ``loop_bodies`` — the multi-tick tail's while body is
      exactly the "launches per decode tick" quantity;
    - ``cond`` branches contribute their maximum (the worst launch
      count a dispatch can pay);
    - a ``pallas_call``'s inner jaxpr is NEVER recursed into — the
      kernel body's ops run inside the one launch being counted.
    """
    pallas = 0
    coll = 0
    bodies = []

    def _sub(j):
        nonlocal pallas, coll
        p, c, b = _census_walk(j)
        bodies.extend(b)
        return p, c

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            pallas += 1
            continue
        if name in COLLECTIVE_PRIMITIVES:
            coll += 1
            continue
        if name == "scan":
            p, c = _sub(eqn.params["jaxpr"].jaxpr)
            n = int(eqn.params["length"])
            pallas += p * n
            coll += c * n
        elif name == "while":
            p, c = _sub(eqn.params["body_jaxpr"].jaxpr)
            bodies.append({"pallas_calls": p, "collectives": c})
            pallas += p
            coll += c
        elif name == "cond":
            per = [_census_walk(br.jaxpr)
                   for br in eqn.params["branches"]]
            for _, _, b in per:
                bodies.extend(b)
            pallas += max(p for p, _, _ in per)
            coll += max(c for _, c, _ in per)
        else:
            # generic containers: pjit, shard_map, custom_{vjp,jvp},
            # remat — recurse every jaxpr-valued param
            for v in eqn.params.values():
                if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                    p, c = _sub(v.jaxpr)
                    pallas += p
                    coll += c
                elif hasattr(v, "eqns"):
                    p, c = _sub(v)
                    pallas += p
                    coll += c
    return pallas, coll, bodies


def jaxpr_census(fn, *args) -> dict:
    """Launch census of one program: trace ``fn`` over ``args``
    (``jax.make_jaxpr`` — a pure retrace that does NOT touch the pjit
    executable cache, so compile-once pins are undisturbed) and count
    the device-side launch structure. Returns::

        {"pallas_calls": int,     # total, scan bodies × trip count
         "collectives": int,      # psum/all_to_all/all_gather/ppermute
         "loop_bodies": [{"pallas_calls": n, "collectives": n}, ...]}

    ``loop_bodies`` holds the PER-ITERATION census of each
    ``while_loop`` body — for the serving multi-tick program that is
    the per-decode-tick launch count, O(num_layers) for the scanned
    layer stack (README "Collective overlap")."""
    closed = jax.make_jaxpr(fn)(*args)
    pallas, coll, bodies = _census_walk(closed.jaxpr)
    return {"pallas_calls": pallas, "collectives": coll,
            "loop_bodies": bodies}


class CostObservatory:
    """Exact per-program dispatch / transfer / compile accounting.

    One observatory is OWNED BY THE GATEWAY and installed on every
    engine incarnation (``engine.cost``), so its counts are monotonic
    across crash-recovery rebuilds — the same ownership rule as the
    tracer and the ``serving_preemptions_total`` base. ``clock`` is any
    zero-arg monotonic-seconds callable (default ``time.perf_counter``;
    tests and the chaos bench pass a
    :class:`~paddle_tpu.serving.faults.VirtualClock`, under which the
    exported accounting replays byte-identically).

    The engine guards every touch on :attr:`enabled` through its
    ``_co()`` helper — one attribute check when disabled, the same
    discipline as the tracer's ``_tr()``.
    """

    def __init__(self, clock=None, ewma_alpha=0.2):
        self.clock = clock if clock is not None else time.perf_counter
        self.enabled = True
        self.ewma_alpha = float(ewma_alpha)
        # label -> per-program record (insertion-ordered: deterministic
        # under a deterministic workload, so export() is byte-stable)
        self.programs = {}
        # step-phase attribution (the engine names the current phase:
        # admit | plan | launch | host-accept): where dispatches land
        self.phases = {}
        self._phase = None
        self.totals = {"dispatches": 0, "h2d_bytes": 0, "d2h_bytes": 0,
                       "compiles": 0, "wall_s": 0.0}
        # cross-chip collective traffic by wire dtype (tensor-parallel
        # engines; README "Tensor-parallel serving") — deliberately a
        # SEPARATE ledger from h2d/d2h: all-reduce bytes never cross
        # the host boundary, and folding them into transfer totals
        # would make the per-program transfer counts unreadable
        self.collectives = {}
        # KV-tier traffic by direction (host-RAM spill tier; README
        # "Tiered KV prefix cache") — the same separate-ledger rule as
        # collectives: spill/readmit bytes ARE host-boundary transfers,
        # but they are cache-plane traffic, not per-program compute
        # I/O, and do not belong in the per-program h2d/d2h records.
        # Directions: "d2h" (spill), "h2d" (readmit), "peer" (fleet
        # host-to-host transfer in).
        self.tiers = {}
        # label -> jaxpr launch census (one per program, recorded
        # lazily on the program's FIRST dispatch through the counting
        # facade — the same chokepoint as every other column, so the
        # in-program launch structure of exactly the programs that ran
        # is what exports)
        self.censuses = {}
        # label -> (jitted fn, abstract args), kept at the program's
        # first dispatch so memory_analysis() can lower it again on
        # demand without touching live (possibly donated) buffers
        self._signatures = {}

    # ------------------------------------------------------------- control
    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def set_phase(self, phase):
        """Name the step phase subsequent dispatches are attributed to
        (None between steps)."""
        self._phase = phase

    # ------------------------------------------------------------ recording
    def wrap(self, key, fn, host_out=()):
        """Counting facade over one jitted program handed out of the
        jit-cache. ``key`` is the cache key (its first element is the
        program kind); ``host_out`` names the result indices the engine
        fetches to host — the exact device→host surface."""
        return _CountedProgram(self, _label(key), str(key[0]), fn,
                               tuple(host_out))

    def _record(self, label, kind, args, out, host_out, compiles, dt):
        h2d = sum(_nbytes(leaf)
                  for leaf in jax.tree_util.tree_leaves(args)
                  if not isinstance(leaf, jax.Array))
        d2h = sum(_nbytes(leaf) for i in host_out
                  for leaf in jax.tree_util.tree_leaves(out[i]))
        rec = self.programs.get(label)
        if rec is None:
            rec = {"kind": kind, "calls": 0, "h2d_bytes": 0,
                   "d2h_bytes": 0, "compiles": 0, "wall_s": 0.0,
                   "wall_ewma_s": None}
            self.programs[label] = rec
        rec["calls"] += 1
        rec["h2d_bytes"] += h2d
        rec["d2h_bytes"] += d2h
        rec["compiles"] += compiles
        rec["wall_s"] += dt
        rec["wall_ewma_s"] = dt if rec["wall_ewma_s"] is None else \
            (1 - self.ewma_alpha) * rec["wall_ewma_s"] + self.ewma_alpha * dt
        t = self.totals
        t["dispatches"] += 1
        t["h2d_bytes"] += h2d
        t["d2h_bytes"] += d2h
        t["compiles"] += compiles
        t["wall_s"] += dt
        ph = self.phases.get(self._phase)
        if ph is None:
            ph = {"dispatches": 0, "h2d_bytes": 0, "d2h_bytes": 0,
                  "wall_s": 0.0}
            self.phases[self._phase] = ph
        ph["dispatches"] += 1
        ph["h2d_bytes"] += h2d
        ph["d2h_bytes"] += d2h
        ph["wall_s"] += dt

    def record_census(self, label, fn, args):
        """Record one program's jaxpr launch census (idempotent per
        label; called by the counting facade on the program's first
        dispatch). The retrace is pure — the pjit executable cache is
        untouched — but it is a retrace, so it runs ONCE per program
        label, never per call. A program whose trace fails under
        ``make_jaxpr`` records ``None`` rather than killing the serving
        step that triggered the census."""
        if label in self.censuses:
            return
        try:
            self.censuses[label] = jaxpr_census(fn, *args)
        except Exception:            # noqa: BLE001 — census is advisory
            self.censuses[label] = None

    def record_signature(self, label, fn, args):
        """Keep one program's jitted callable and abstract argument
        shapes (idempotent per label; called by the counting facade
        BEFORE the program's first dispatch, while donated arguments
        are still alive)."""
        if label not in self._signatures:
            self._signatures[label] = (fn, jax.tree_util.tree_map(
                _abstract, args))

    def memory_analysis(self) -> dict:
        """Compiled-memory footprint of every program that dispatched:
        ``{label: {argument_bytes, output_bytes, alias_bytes,
        temp_bytes}}`` from the compiler's own ``memory_analysis()``.
        Computed ON DEMAND (``GET /debug/profile?memory=1``): each
        program is lowered again from its abstract signature and
        compiled — a load from the persistent compile cache where that
        is on, a real compile otherwise — so serving pays nothing until
        someone asks. A program the compiler refuses here reports its
        error instead of failing the debug request."""
        out = {}
        for label, (fn, abstract) in list(self._signatures.items()):
            try:
                m = fn.lower(*abstract).compile().memory_analysis()
                out[label] = {
                    "argument_bytes": int(m.argument_size_in_bytes),
                    "output_bytes": int(m.output_size_in_bytes),
                    "alias_bytes": int(m.alias_size_in_bytes),
                    "temp_bytes": int(m.temp_size_in_bytes)}
            except Exception as e:   # noqa: BLE001 — debug surface
                out[label] = {"error": f"{type(e).__name__}: {e}"[:300]}
        return out

    def record_collective(self, dtype, ops, nbytes):
        """Account one sharded launch's cross-chip all-reduce traffic:
        ``ops`` collective operations moving ``nbytes`` wire bytes per
        device, under wire-dtype label ``dtype`` (``fp`` | ``int8``).
        Shape-derived by the caller (the engine's
        ``_record_collectives``) — exact and deterministic, no network
        probe. The ``serving_collective_bytes_total{dtype}`` counter
        and the ``/debug/profile`` collectives section read this."""
        rec = self.collectives.get(dtype)
        if rec is None:
            rec = {"ops": 0, "bytes": 0}
            self.collectives[dtype] = rec
        rec["ops"] += int(ops)
        rec["bytes"] += int(nbytes)

    def collective_bytes(self, dtype) -> int:
        """Total wire bytes recorded under one collective dtype (0 for
        a dtype that never ran — tp=1 engines scrape explicit zeros)."""
        rec = self.collectives.get(dtype)
        return int(rec["bytes"]) if rec else 0

    def record_tier(self, direction, blocks, nbytes):
        """Account KV-tier cache-plane traffic: ``blocks`` pool blocks
        moving ``nbytes`` bytes under ``direction`` (``d2h`` spill |
        ``h2d`` readmit | ``peer`` fleet transfer in). Shape-derived by
        the caller (the prefix cache's spill/readmit paths) — exact and
        deterministic. The ``serving_tier_bytes_total{direction}``
        counter and the ``/debug/profile`` tiers section read this."""
        rec = self.tiers.get(direction)
        if rec is None:
            rec = {"blocks": 0, "bytes": 0}
            self.tiers[direction] = rec
        rec["blocks"] += int(blocks)
        rec["bytes"] += int(nbytes)

    def tier_bytes(self, direction) -> int:
        """Total bytes recorded under one tier direction (0 for a
        direction that never moved — tierless engines scrape explicit
        zeros)."""
        rec = self.tiers.get(direction)
        return int(rec["bytes"]) if rec else 0

    # -------------------------------------------------------------- reading
    def kind_calls(self, kind) -> int:
        """Total dispatches of one program kind (the
        ``serving_dispatches_total{program}`` series). ``list()``
        snapshots the dict before iterating: scrapes run on HTTP
        handler threads while the driver may be inserting a new
        program label, and bare dict iteration would raise
        "changed size during iteration"."""
        return sum(rec["calls"] for rec in list(self.programs.values())
                   if rec["kind"] == kind)

    def snapshot(self) -> dict:
        """Cheap totals copy — the engine's per-step delta base."""
        return dict(self.totals)

    def delta(self, base) -> dict:
        """Totals accrued since ``base`` (a prior :meth:`snapshot`)."""
        return {k: self.totals[k] - base[k]
                for k in ("dispatches", "h2d_bytes", "d2h_bytes",
                          "compiles")}

    def snapshot_full(self) -> dict:
        """Deep copy of the whole accounting — the base (or frozen end)
        of a step-bounded ``/debug/profile`` capture window. ``list()``
        snapshots each dict before iterating (see :meth:`kind_calls`);
        concurrent driver updates can tear a single in-flight record,
        never crash."""
        return {"programs": {k: dict(v)
                             for k, v in list(self.programs.items())},
                "phases": {k: dict(v)
                           for k, v in list(self.phases.items())},
                "totals": dict(self.totals),
                "collectives": {k: dict(v)
                                for k, v in list(
                                    self.collectives.items())},
                "tiers": {k: dict(v)
                          for k, v in list(self.tiers.items())},
                "censuses": {k: (dict(v) if v is not None else None)
                             for k, v in list(self.censuses.items())}}

    def export(self, base=None, at=None) -> dict:
        """The cost-attribution document: aggregate, the delta since
        ``base``, or the ``base``→``at`` window (both prior
        :meth:`snapshot_full` snapshots — ``at`` is how a step-bounded
        capture freezes its END at the exact step boundary instead of
        leaking later steps into the window). Deterministic for a
        deterministic workload: insertion-ordered programs, rounded
        floats, no wall-clock reads."""
        state = at if at is not None else self.snapshot_full()
        base_p = (base or {}).get("programs", {})
        base_t = (base or {}).get("totals", {})
        base_ph = (base or {}).get("phases", {})
        wall_total = state["totals"]["wall_s"] - base_t.get("wall_s", 0.0)
        programs = []
        for label, rec in state["programs"].items():
            b = base_p.get(label, {})
            calls = rec["calls"] - b.get("calls", 0)
            if calls <= 0:
                continue
            wall = rec["wall_s"] - b.get("wall_s", 0.0)
            entry = {
                "program": label, "kind": rec["kind"], "calls": calls,
                "h2d_bytes": rec["h2d_bytes"] - b.get("h2d_bytes", 0),
                "d2h_bytes": rec["d2h_bytes"] - b.get("d2h_bytes", 0),
                "compiles": rec["compiles"] - b.get("compiles", 0),
                "wall_s": round(wall, 9),
                "wall_ewma_s": round(rec["wall_ewma_s"] or 0.0, 9),
                "share_of_wall": round(wall / wall_total, 6)
                if wall_total > 0 else 0.0,
            }
            census = state.get("censuses", {}).get(label)
            if census is not None:
                entry["census"] = census
            programs.append(entry)
        programs.sort(key=lambda r: (-r["wall_s"], -r["calls"],
                                     r["program"]))
        phases = {}
        for name, rec in state["phases"].items():
            b = base_ph.get(name, {})
            d = rec["dispatches"] - b.get("dispatches", 0)
            if d <= 0:
                continue
            phases[str(name)] = {
                "dispatches": d,
                "h2d_bytes": rec["h2d_bytes"] - b.get("h2d_bytes", 0),
                "d2h_bytes": rec["d2h_bytes"] - b.get("d2h_bytes", 0),
                "wall_s": round(rec["wall_s"] - b.get("wall_s", 0.0), 9),
            }
        totals = {k: state["totals"][k] - base_t.get(k, 0)
                  for k in ("dispatches", "h2d_bytes", "d2h_bytes",
                            "compiles")}
        totals["wall_s"] = round(wall_total, 9)
        base_c = (base or {}).get("collectives", {})
        collectives = {}
        for dtype, rec in state.get("collectives", {}).items():
            b = base_c.get(dtype, {})
            d_ops = rec["ops"] - b.get("ops", 0)
            d_bytes = rec["bytes"] - b.get("bytes", 0)
            if d_ops <= 0 and d_bytes <= 0:
                continue
            collectives[dtype] = {"ops": d_ops, "bytes": d_bytes}
        base_tr = (base or {}).get("tiers", {})
        tiers = {}
        for direction, rec in state.get("tiers", {}).items():
            b = base_tr.get(direction, {})
            d_blocks = rec["blocks"] - b.get("blocks", 0)
            d_bytes = rec["bytes"] - b.get("bytes", 0)
            if d_blocks <= 0 and d_bytes <= 0:
                continue
            tiers[direction] = {"blocks": d_blocks, "bytes": d_bytes}
        return {"programs": programs, "phases": phases, "totals": totals,
                "collectives": collectives, "tiers": tiers}


class _CountedProgram:
    """The counting facade: calls the wrapped jitted program and
    records exact dispatch/byte/compile/wall accounting. Handed out
    fresh per accessor call (the jit-cache keeps the RAW jitted fn, so
    ``decode_compilations()`` / shared-cache semantics are
    untouched)."""

    __slots__ = ("_co", "_label", "_kind", "_fn", "_host_out")

    def __init__(self, co, label, kind, fn, host_out):
        self._co = co
        self._label = label
        self._kind = kind
        self._fn = fn
        self._host_out = host_out

    def _cache_size(self):
        # transparent to compile-count assertions made on a handout
        return self._fn._cache_size()

    def __call__(self, *args):
        co = self._co
        fn = self._fn
        if self._label not in co._signatures:
            co.record_signature(self._label, fn, args)
        t0 = co.clock()
        c0 = fn._cache_size()
        out = fn(*args)
        co._record(self._label, self._kind, args, out, self._host_out,
                   fn._cache_size() - c0, co.clock() - t0)
        # jaxpr launch census, once per program label (idempotent):
        # the facade call IS the chokepoint every jit-cache handout
        # funnels through, so the census covers exactly the programs
        # that dispatched — and the retrace it costs is paid once,
        # after the real call, never on the steady-state path
        if self._label not in co.censuses:
            co.record_census(self._label, fn, args)
        return out
