"""paddle_tpu.jit — the compiled execution path.

Replaces the reference's static-graph stack (dy2static AST transform +
Executor/InterpreterCore, ``python/paddle/jit``) with direct jax tracing:

- :func:`to_static` — compile a Layer or function's forward (inference path).
- :class:`TrainStep` — compile the full train step (forward + backward +
  optimizer update, optionally AMP and mesh shardings) into ONE XLA program.
  This is the TPU answer to Paddle's per-op eager dispatch: instead of making
  dispatch fast, there is no per-op dispatch in steady state at all.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..autograd.engine import no_grad
from ..core import random as random_mod
from ..core.random import rng_scope
from ..core.tensor import Tensor
from ..optimizer.lr import LRScheduler
from . import functional as func_mod
from .functional import bind, call_functional, rebind_results, split_state

_tensor_leaf = lambda t: isinstance(t, Tensor)


def _norm_batch(inputs):
    return _unwrap(inputs if isinstance(inputs, tuple) else (inputs,))


def _clean_spec(spec, value, axis_names):
    """Drop axis names not present in the mesh; pad/truncate to value rank."""
    from jax.sharding import PartitionSpec as P
    if spec is None:
        return None
    parts = list(spec)
    parts = parts[:value.ndim] + [None] * (value.ndim - len(parts))
    out = []
    for s in parts:
        if isinstance(s, str) and s not in axis_names:
            out.append(None)
        elif isinstance(s, tuple):
            kept = tuple(n for n in s if n in axis_names)
            out.append(kept if kept else None)
        else:
            out.append(s)
    return P(*out)


def _norm_labels(labels):
    labels = _unwrap(labels if isinstance(labels, tuple) else (labels,))
    return labels if len(labels) > 1 else labels[0]


def _unwrap(tree):
    return jax.tree.map(lambda t: t.value if isinstance(t, Tensor) else t,
                        tree, is_leaf=_tensor_leaf)


_TO_STATIC_ENABLED = [True]


def enable_to_static(flag):
    """Global to_static switch (reference paddle.jit.enable_to_static †):
    when False, decorated callables run eagerly — the standard debugging
    escape hatch for translated programs."""
    _TO_STATIC_ENABLED[0] = bool(flag)


def ignore_module(modules):
    """Accepted for reference parity (paddle.jit.ignore_module †). The
    AST translator skips these modules' source; the tracing design here
    has no per-module translation to skip, so registration is a no-op."""
    return None


def to_static(function=None, input_spec=None, build_strategy=None,
              full_graph=True, backend=None):
    """paddle.jit.to_static — returns a compiled callable.

    For a Layer, compiles ``forward`` (buffers threaded functionally and
    written back after each call). For a plain function over Tensors,
    jit-compiles it directly. ``enable_to_static(False)`` makes the
    returned callable run the original eager code instead.
    """
    def decorate(obj):
        from ..nn.layer import Layer
        if isinstance(obj, Layer):
            return StaticLayer(obj)

        compiled = {}

        def wrapper(*args, **kwargs):
            if not _TO_STATIC_ENABLED[0]:
                # same detach semantics as the compiled path (which traces
                # under no_grad): the switch changes execution mode only
                with no_grad():
                    return obj(*args, **kwargs)

            def pure(vals, kw):
                with no_grad():
                    t_args = jax.tree.map(Tensor, vals)
                    t_kw = jax.tree.map(Tensor, kw)
                    out = obj(*t_args, **t_kw)
                return _unwrap(out)

            if "fn" not in compiled:
                compiled["fn"] = jax.jit(pure)
            out = compiled["fn"](_unwrap(args), _unwrap(kwargs))
            return jax.tree.map(Tensor, out)

        wrapper.__wrapped__ = obj
        return wrapper

    if function is not None:
        return decorate(function)
    return decorate


class StaticLayer:
    """Compiled wrapper around a Layer's forward (inference/eval path)."""

    def __init__(self, layer):
        self._layer = layer
        self._jit = jax.jit(self._pure, static_argnames=("training",))

    def _pure(self, params, buffers, args, key, training):
        with rng_scope(key):
            prev = self._layer.training
            if training:
                self._layer.train()
            else:
                self._layer.eval()
            try:
                out, new_buffers = call_functional(self._layer, params, buffers, args)
            finally:
                if prev:
                    self._layer.train()
                else:
                    self._layer.eval()
        return out, new_buffers

    def __call__(self, *args):
        if not _TO_STATIC_ENABLED[0]:
            # debugging escape hatch: run the original eager forward with
            # the compiled path's detach semantics (it traces under
            # no_grad), so the switch changes execution mode only
            with no_grad():
                return self._layer(*args)
        params, buffers = split_state(self._layer)
        key = random_mod.next_key()
        out, new_buffers = self._jit(params, buffers, _unwrap(args), key,
                                     self._layer.training)
        rebind_results(self._layer, params, new_buffers)
        return jax.tree.map(Tensor, out)

    def __getattr__(self, name):
        return getattr(self._layer, name)


class TrainStep:
    """One-shot compiled train step.

    ``step(inputs, labels)`` runs: forward -> loss -> backward -> grad clip ->
    optimizer -> buffer update, all inside a single jitted XLA program with
    donated buffers (in-place param updates on device, no host round-trips).

    Parameters mirror the pieces a Fleet trainer wires together; hybrid
    parallel wrappers pass ``mesh``/spec functions so GSPMD lays out the same
    program over a TPU slice.
    """

    def __init__(self, model, loss_fn: Callable, optimizer, *,
                 mesh=None, batch_axes=None, sharding_stage: int = 0,
                 param_spec_fn=None, grad_accum_steps: int = 1,
                 donate: bool = True, loss_scale=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh
        self._step_count = 0
        self._base_key = random_mod.next_key()
        params, buffers = split_state(model)
        if donate:
            # Private copies: donated buffers get deleted in place, and the
            # originals may be aliased by other Tensors (state_dict sharing).
            # After each step the model is re-pointed at the fresh outputs,
            # so steady-state memory is 1x.
            params = jax.tree.map(jnp.copy, params)
            buffers = jax.tree.map(jnp.copy, buffers)

        # ------------------------------------------------------ mesh placement
        # Parameters carry PartitionSpecs (mp layers set .dist_spec); ZeRO
        # stages add 'sharding'-axis specs (params stage>=3, opt slots
        # stage>=1). Placement = committed shardings on the input arrays; XLA
        # GSPMD propagates them through the step (completion+partitioner+
        # reshard of the reference's auto-parallel engine, SURVEY.md §3.4).
        self._batch_spec = None
        if mesh is not None:
            from ..parallel import sharding_api as zsh
            axis_names = set(mesh.axis_names)
            if batch_axes is None:
                batch_axes = tuple(a for a in ("dp", "sharding")
                                   if a in axis_names and mesh.shape[a] > 1) \
                    or tuple(a for a in ("dp",) if a in axis_names)
            self._batch_spec = (tuple(batch_axes) if len(batch_axes) > 1
                                else (batch_axes[0] if batch_axes else None))
            shard_deg = mesh.shape.get("sharding", 1)
            param_objs = {n: p for n, p in model.named_parameters()
                          if not p.stop_gradient}

            def pspec(name, value):
                base = getattr(param_objs.get(name), "dist_spec", None)
                if param_spec_fn is not None:
                    base = param_spec_fn(name, value) or base
                base = _clean_spec(base, value, axis_names)
                return zsh.param_spec_for_stage(value.shape, base,
                                                sharding_stage, shard_deg)

            self._param_specs = {n: pspec(n, v) for n, v in params.items()}
            params = {n: jax.device_put(v, NamedSharding(
                mesh, self._param_specs[n] or P())) for n, v in params.items()}
            repl = NamedSharding(mesh, P())
            buffers = {n: jax.device_put(v, repl) for n, v in buffers.items()}
        self._params = params
        self._buffers = buffers
        self._opt_state = optimizer.init_state(params)
        if mesh is not None:
            from ..parallel import sharding_api as zsh
            shard_deg = mesh.shape.get("sharding", 1)
            slots = {}
            for n, slotd in self._opt_state["slots"].items():
                spec = zsh.opt_state_spec(params[n].shape,
                                          self._param_specs.get(n),
                                          max(sharding_stage, 1) if shard_deg > 1
                                          else 0, shard_deg)
                sh = NamedSharding(mesh, spec or P())
                slots[n] = {k: jax.device_put(v, sh) for k, v in slotd.items()}
            self._opt_state = {"slots": slots, "step": self._opt_state["step"]}
        self._grad_accum = grad_accum_steps
        self.loss_scale = loss_scale  # amp.GradScaler for fp16 (bf16 needs none)

        model_ref = model
        loss_ref = loss_fn

        def loss_f(p, b, inputs, labels, key):
            with rng_scope(key), no_grad():
                with bind(model_ref, p, b) as collect:
                    t_in = jax.tree.map(Tensor, inputs)
                    out = model_ref(*t_in) if isinstance(t_in, tuple) else model_ref(t_in)
                    t_lab = jax.tree.map(Tensor, labels)
                    with jax.named_scope("loss"):
                        if isinstance(t_lab, tuple):
                            loss = loss_ref(out, *t_lab)
                        else:
                            loss = loss_ref(out, t_lab)
                    new_b = collect()
            lv = loss.value if isinstance(loss, Tensor) else loss
            return lv.astype(jnp.float32), new_b

        opt = optimizer

        def apply_update(p, grads, opt_state, lr):
            # scopes are compile-time metadata: in a device trace they tell
            # the update's ops from the backward pass (``transpose(...)`` in
            # an op's name) and the recomputed forward inside it
            # (``rematted_computation``)
            with jax.named_scope("optimizer"):
                return opt.apply_gradients(p, grads, opt_state, lr)

        # Debug NaN/Inf guard (reference FLAGS_check_nan_inf /
        # ``paddle/fluid/framework/details/nan_inf_utils_detail`` †): when
        # the flag is on at construction, the compiled step also returns a
        # non-finite count over loss+grads and step() raises host-side.
        from ..utils.flags import get_flag
        self._check_nan = bool(get_flag("FLAGS_check_nan_inf", False))
        check_nan = self._check_nan

        def _bad_count(loss, grads):
            if not check_nan:
                return jnp.zeros((), jnp.int32)
            bad = jnp.sum(~jnp.isfinite(loss)).astype(jnp.int32)
            for g in jax.tree.leaves(grads):
                if jnp.issubdtype(jnp.result_type(g), jnp.inexact):
                    bad = bad + jnp.sum(~jnp.isfinite(g)).astype(jnp.int32)
            return bad

        def step_fn(p, b, opt_state, inputs, labels, lr, key):
            (loss, new_b), grads = jax.value_and_grad(loss_f, has_aux=True)(
                p, b, inputs, labels, key)
            bad = _bad_count(loss, grads)
            new_p, new_opt = apply_update(p, grads, opt_state, lr)
            return loss, new_p, new_b, new_opt, bad

        donate_argnums = (0, 1, 2) if donate else ()
        self._compiled = jax.jit(step_fn, donate_argnums=donate_argnums)

        def accum_step_fn(p, b, opt_state, inputs, labels, lr, key, accum):
            # reshape batch dim -> (accum, micro, ...) and lax.scan over
            # microbatches, accumulating grads (the compiled analog of the
            # reference's 1F1B/gradient-merge accumulation)
            def resh(x):
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])
            inputs_m = jax.tree.map(resh, inputs)
            labels_m = jax.tree.map(resh, labels)
            zero_g = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), p)

            def micro(carry, xs):
                g_acc, b_cur, loss_acc, i = carry
                mb_in, mb_lab = xs
                k = jax.random.fold_in(key, i)
                (loss, new_b), grads = jax.value_and_grad(
                    loss_f, has_aux=True)(p, b_cur, mb_in, mb_lab, k)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                return (g_acc, new_b, loss_acc + loss, i + 1), None

            (g_sum, new_b, loss_sum, _), _ = jax.lax.scan(
                micro, (zero_g, b, jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.int32)),
                (inputs_m, labels_m))
            grads = jax.tree.map(lambda g: g / accum, g_sum)
            bad = _bad_count(loss_sum, grads)
            new_p, new_opt = apply_update(p, grads, opt_state, lr)
            return loss_sum / accum, new_p, new_b, new_opt, bad

        self._accum_compiled = jax.jit(
            accum_step_fn, donate_argnums=donate_argnums,
            static_argnames=("accum",))

        def eval_fn(p, b, inputs, labels, key):
            return loss_f(p, b, inputs, labels, key)[0]

        self._compiled_eval = jax.jit(eval_fn)

    # -------------------------------------------------------------- stepping
    def _place_batch(self, tree):
        """Commit batch arrays to the mesh with the dp(+sharding) sharding."""
        if self.mesh is None or self._batch_spec is None:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec as P

        def put(a):
            if getattr(a, "ndim", 0) >= 1:
                spec = P(self._batch_spec, *([None] * (a.ndim - 1)))
                return jax.device_put(a, NamedSharding(self.mesh, spec))
            return a

        return jax.tree.map(put, tree)

    def __call__(self, inputs, labels):
        return self.step(inputs, labels)

    def step(self, inputs, labels):
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = jax.random.fold_in(self._base_key, self._step_count)
        inputs, labels = _norm_batch(inputs), _norm_labels(labels)
        inputs, labels = self._place_batch(inputs), self._place_batch(labels)
        with jax.profiler.StepTraceAnnotation("train_step",
                                              step_num=self._step_count):
            loss, self._params, self._buffers, self._opt_state, bad = \
                self._compiled(self._params, self._buffers,
                               self._opt_state, inputs, labels, lr, key)
        self._step_count += 1
        self.optimizer._step_count = self._step_count
        self.sync_to_model()
        self._raise_on_nan(bad, loss)
        return Tensor(loss)

    def _raise_on_nan(self, bad, loss):
        if self._check_nan and int(bad) > 0:
            raise RuntimeError(
                f"FLAGS_check_nan_inf: {int(bad)} non-finite value(s) in "
                f"loss/gradients at step {self._step_count} "
                f"(loss={float(loss)})")

    def accum_step(self, inputs, labels, accum: int):
        """Gradient-accumulating step: `accum` microbatches, one update."""
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        key = jax.random.fold_in(self._base_key, self._step_count)
        inputs, labels = _norm_batch(inputs), _norm_labels(labels)
        inputs, labels = self._place_batch(inputs), self._place_batch(labels)
        loss, self._params, self._buffers, self._opt_state, bad = \
            self._accum_compiled(
                self._params, self._buffers, self._opt_state, inputs, labels,
                lr, key, int(accum))
        self._step_count += 1
        self.optimizer._step_count = self._step_count
        self.sync_to_model()
        self._raise_on_nan(bad, loss)
        return Tensor(loss)

    def eval_step(self, inputs, labels):
        key = jax.random.fold_in(self._base_key, self._step_count)
        inputs, labels = _norm_batch(inputs), _norm_labels(labels)
        inputs, labels = self._place_batch(inputs), self._place_batch(labels)
        loss = self._compiled_eval(self._params, self._buffers, inputs,
                                   labels, key)
        return Tensor(loss)

    def compile_step(self, inputs, labels):
        """The train step compiled ahead of time for these batch shapes,
        without running it: ``.as_text()`` is the optimized HLO,
        ``.memory_analysis()`` the compiler's argument/temp byte counts."""
        lr = jnp.zeros((), jnp.float32)
        key = jax.random.PRNGKey(0)
        inputs, labels = _norm_batch(inputs), _norm_labels(labels)
        inputs, labels = self._place_batch(inputs), self._place_batch(labels)
        return self._compiled.lower(self._params, self._buffers,
                                    self._opt_state, inputs, labels, lr,
                                    key).compile()

    def lower_text(self, inputs, labels) -> str:
        """Compiled HLO of the train step — for compile-only tests
        asserting collective placement (SURVEY.md §4 pattern 3)."""
        return self.compile_step(inputs, labels).as_text()

    def sync_to_model(self):
        """Write the device-side params/buffers back into the Layer tree
        (for checkpointing / switching back to eager)."""
        rebind_results(self.model, self._params, self._buffers)

    @property
    def params(self):
        return self._params

    @property
    def opt_state(self):
        return self._opt_state


def _struct_from_shape(dims, dt, pos, scope):
    """(dims with -1 dynamics, dtype) -> jax.ShapeDtypeStruct. Dynamic
    dims become jax.export symbolic dimensions in the SHARED ``scope``
    (mixing scopes across inputs is rejected). A dynamic AXIS-0 dim uses
    one shared symbol across all inputs — multi-input models share their
    batch axis, and independent symbols would fail export-time shape
    checks on any op combining two inputs; non-leading dynamic dims stay
    independent (varlen axes need not agree)."""
    if not any(d == -1 for d in dims):
        return jax.ShapeDtypeStruct(tuple(dims), dt)
    from jax import export as jexport
    sym = ",".join(("_dynb" if i == 0 else f"_dyn{pos}_{i}") if d == -1
                   else str(d) for i, d in enumerate(dims))
    return jax.ShapeDtypeStruct(jexport.symbolic_shape(sym, scope=scope),
                                dt)


def _spec_struct(s, pos, scope):
    """InputSpec / Tensor / array-like -> jax.ShapeDtypeStruct (dynamic
    dims via :func:`_struct_from_shape`)."""
    from ..core import dtype as dtype_mod
    if isinstance(s, Tensor):
        return jax.ShapeDtypeStruct(tuple(s.shape), s.value.dtype)
    dims = [int(d) if d is not None else -1 for d in s.shape]
    dt = dtype_mod.to_jax_dtype(getattr(s, "dtype", "float32"))
    return _struct_from_shape(dims, dt, pos, scope)


def save(layer, path, input_spec=None, **config):
    """paddle.jit.save (reference: python/paddle/jit/api.py † — persists a
    translated static program + params). TPU-native artifact split:

    - ``<path>.pdparams`` — the state dict (train/finetune state).
    - ``<path>.pdmodel`` — when ``input_spec`` is given, the layer's
      forward traced once and serialized as StableHLO via ``jax.export``
      (the XLA analog of the reference's translated program; weights are
      baked in as constants, so the .pdmodel alone is a complete
      inference artifact loadable by :func:`load`).
    """
    import os as _os

    from ..framework import io as fio
    base = path[:-len(".pdparams")] if path.endswith(".pdparams") else path
    state = layer.state_dict() if hasattr(layer, "state_dict") else layer
    fio.save(state, base + ".pdparams")
    if input_spec is None:
        # params-only save must not leave a stale traced program behind —
        # a later load would silently run the OLD baked weights
        if _os.path.exists(base + ".pdmodel"):
            _os.remove(base + ".pdmodel")
        return
    if not callable(layer):
        raise TypeError(
            f"jit.save: input_spec was given but the object to save is not "
            f"callable ({type(layer).__name__}); pass the Layer itself, not "
            f"its state_dict, to export a traced program")
    from jax import export as jexport

    def _pure(*arrs):
        with no_grad():
            return _unwrap(layer(*[Tensor(a) for a in arrs]))

    # trace in eval mode: an inference artifact must not bake in dropout,
    # and a train-mode BatchNorm would _rebind its running stats with the
    # export tracer (leaking it into the live layer's buffers)
    was_training = bool(getattr(layer, "training", False))
    if hasattr(layer, "eval"):
        layer.eval()
    try:
        scope = jexport.SymbolicScope()
        exp = jexport.export(jax.jit(_pure))(
            *[_spec_struct(s, i, scope) for i, s in enumerate(input_spec)])
    finally:
        if was_training and hasattr(layer, "train"):
            layer.train()
    with open(base + ".pdmodel", "wb") as f:
        f.write(exp.serialize())


class TranslatedLayer:
    """Callable inference artifact returned by :func:`load` (reference
    ``paddle.jit.TranslatedLayer`` †): wraps a deserialized StableHLO
    program. Weights are constants inside the program; ``state_dict()``
    exposes the sidecar .pdparams for inspection/finetune hand-off."""

    def __init__(self, exported, state):
        self._exported = exported
        self._state = state
        self.training = False

    def __call__(self, *args):
        arrs = [a.value if isinstance(a, Tensor) else jnp.asarray(a)
                for a in args]
        out = self._exported.call(*arrs)
        return jax.tree.map(lambda v: Tensor(v), out)

    forward = __call__

    def state_dict(self):
        if self._state is None:
            raise FileNotFoundError(
                "TranslatedLayer.state_dict(): this artifact was loaded "
                "from a .pdmodel with no .pdparams sidecar (the exported "
                "program is self-contained — weights are baked in as "
                "constants, so inference works without it). To get a state "
                "dict for inspection or finetune hand-off, re-save with "
                "jit.save(layer, path) so the .pdparams sidecar is written "
                "next to the .pdmodel")
        return self._state

    def eval(self):
        self.training = False
        return self

    def train(self):
        raise RuntimeError(
            "TranslatedLayer is an inference artifact (weights are baked "
            "into the exported program); rebuild the python Layer and "
            "set_state_dict the .pdparams to train")


def load(path, **config):
    """Returns a callable :class:`TranslatedLayer` when a traced program
    was saved (input_spec passed to save); otherwise the bare state dict
    (params-only save)."""
    import os as _os

    from ..framework import io as fio
    p = path if path.endswith(".pdparams") else path + ".pdparams"
    # the .pdmodel alone is a complete inference artifact (weights baked
    # in), so a missing params sidecar is fine when the program exists
    state = fio.load(p) if _os.path.exists(p) else None
    model_p = (path[:-len(".pdparams")] if path.endswith(".pdparams")
               else path) + ".pdmodel"
    if _os.path.exists(model_p):
        from jax import export as jexport
        with open(model_p, "rb") as f:
            exported = jexport.deserialize(f.read())
        return TranslatedLayer(exported, state)
    if state is None:
        raise FileNotFoundError(f"no {p} or {model_p}")
    return state


def not_to_static(fn):
    return fn
