"""paddle_tpu.jit — whole-graph compilation (`to_static`) + compiled train steps.

Analog of /root/reference/python/paddle/jit/ (34.7K LoC: SOT bytecode
capture + AST dy2static, python/paddle/jit/dy2static/partial_program.py:231).
The TPU-native design needs none of that machinery: eager ops already run on
jax arrays, so `to_static` simply traces the Layer/function under `jax.jit`
— parameters and buffers enter as pytree *inputs* (so optimizer updates
never trigger recompilation) and the compiled region composes with the eager
tape through one GradNode whose backward is the XLA-compiled VJP (the analog
of the reference's RunProgramGradNode,
paddle/fluid/eager/to_static/run_program_op_node.h).

`TrainStep` goes further and fuses forward + backward + optimizer update
into ONE donated-buffer XLA program — whole-step compilation is the
performance story on TPU (SURVEY.md §7 M2).
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..core import autograd, random as _random
from ..core.autograd import GradNode
from ..core.tensor import Tensor, TracedConcretizationError
from ..profiler import annotate, note_program

__all__ = [
    "to_static", "TrainStep", "cond", "while_loop", "scan",
    "ignore_module", "not_to_static", "StaticFunction",
    "enable_compilation_cache",
]


# where the persistent compile cache lives when the environment does not
# say: one fixed directory at the root of the checkout (git-ignored). The
# path is part of the cache key, so it is never a temp name, pid or time.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache():
    """Switch on JAX's persistent compilation cache so compiled programs
    (including the serving engine's AOT ``warmup()`` shapes) survive
    process restarts — a restarted server replays its warmup from disk
    instead of re-invoking XLA per shape.

    The directory is placed from OUTSIDE the program: where
    ``JAX_COMPILATION_CACHE_DIR`` is set it is that directory, otherwise
    ``<checkout>/.jax_cache``. This is the only place the program sets
    ``jax_compilation_cache_dir``. Programs of any size and compile time
    are cached (JAX's default thresholds would skip the small per-width
    prefill shapes). Call it before the first compile that should be
    cached; safe to call repeatedly. Returns the directory in use."""
    from jax.experimental.compilation_cache import compilation_cache

    directory = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or _CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches cache initialization at the FIRST compile of the
    # process: if anything compiled before this call (model init alone
    # compiles), the directory is ignored until the cache is reset
    compilation_cache.reset_cache()
    return directory


# ------------------------------------------------------------ traced RNG

@contextlib.contextmanager
def _traced_rng(base_key):
    """Swap the global RNG root for a traced key while tracing so stateful
    random ops (dropout without explicit keys) consume traced randomness
    instead of baking a constant mask into the compiled program. The host
    counter still increments per call site, giving each random op in the
    graph a distinct fold-in of the traced base key."""
    saved = (_random._rng.key, _random._rng.counter,
             _random._trace_state.flag)
    _random._rng.key = base_key
    _random._rng.counter = 0
    _random._trace_state.flag = True
    try:
        yield
    finally:
        (_random._rng.key, _random._rng.counter,
         _random._trace_state.flag) = saved


def _as_tensor_tree(tree):
    return jax.tree_util.tree_map(
        lambda v: Tensor._from_value(v) if isinstance(v, jax.Array) else v,
        tree,
    )


def _as_array_tree(tree):
    return jax.tree_util.tree_map(
        lambda v: v._value if isinstance(v, Tensor) else v,
        tree,
        is_leaf=lambda v: isinstance(v, Tensor),
    )


from ..ops.registry import _freeze  # shared cache-key freezer


_IS_TENSOR = lambda v: isinstance(v, Tensor)  # noqa: E731


def _loaded_global_names(code):
    """Names the bytecode resolves via LOAD_GLOBAL, recursing into nested
    code objects (lambdas/comprehensions/genexps) — co_names alone also
    contains ATTRIBUTE names, which must not pull in unrelated globals."""
    import dis
    import types

    names = set()
    for ins in dis.get_instructions(code):
        if ins.opname == "LOAD_GLOBAL":
            names.add(ins.argval)
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            names |= _loaded_global_names(const)
    return names


def _closure_layers(fn):
    """Layers a plain function references via its closure cells or module
    globals — the parameters the reference's dy2static still trains when a
    decorated FUNCTION (not a Layer method) closes over a model. Resolved
    lazily at CALL time by StaticFunction, so globals assigned or swapped
    after decoration are seen."""
    from ..nn import Layer

    found = []

    def visit(v):
        if isinstance(v, Layer) and all(v is not f for f in found):
            found.append(v)

    for cell in getattr(fn, "__closure__", None) or ():
        try:
            visit(cell.cell_contents)
        except ValueError:
            continue
    code = getattr(fn, "__code__", None)
    glb = getattr(fn, "__globals__", None)
    if code is not None and glb is not None:
        for name in sorted(_loaded_global_names(code)):
            visit(glb.get(name))
    return found


# Guards the swap-run-restore window below. The swap mutates the LIVE
# Layer's parameters, so two threads tracing the same model concurrently
# (e.g. two serving engines sharing weights, each behind an RPC dispatcher
# worker) would read each other's tracers out of the shared object —
# escaping their trace as an UnexpectedTracerError. RLock: a traced
# forward may re-enter for a nested _FunctionalModel. Held only while
# Python runs the forward (trace time / eager fallback); steady-state
# compiled dispatch never takes it.
_swap_lock = threading.RLock()


class _FunctionalModel:
    """Pure-function view of a Layer (or plain function): swap traced arrays
    into the live Parameters, run forward, capture buffer updates, restore.
    A plain function's closure-captured Layers are tracked too (their
    params enter as pytree inputs keyed ``{i}:{name}``), so gradients flow
    instead of the params being baked in as constants."""

    def __init__(self, layer, fn=None, closure_layers=()):
        self.layer = layer
        self.fn = fn
        self.closure_layers = list(closure_layers)

    def named_closure_params(self):
        return {f"{i}:{k}": p
                for i, lay in enumerate(self.closure_layers)
                for k, p in lay.named_parameters()}

    def named_closure_buffers(self):
        return {f"{i}:{k}": b
                for i, lay in enumerate(self.closure_layers)
                for k, b in lay.named_buffers()}

    def _call_fn_mode(self, params, buffers, args, kwargs, rng_key):
        with _swap_lock:
            return self._call_fn_mode_locked(params, buffers, args, kwargs,
                                             rng_key)

    def _call_fn_mode_locked(self, params, buffers, args, kwargs, rng_key):
        layers = self.closure_layers
        saved = [(dict((k, p._value) for k, p in lay.named_parameters()),
                  dict((k, b._value) for k, b in lay.named_buffers()))
                 for lay in layers]
        buffer_objs = self.named_closure_buffers()
        saved_managed = _random._trace_state.managed_buffers
        try:
            for i, lay in enumerate(layers):
                pre = f"{i}:"
                lay.load_raw_state(
                    {k[len(pre):]: v for k, v in params.items()
                     if k.startswith(pre)},
                    {k[len(pre):]: v for k, v in buffers.items()
                     if k.startswith(pre)})
            _random._trace_state.managed_buffers = saved_managed | {
                id(b) for b in buffer_objs.values()}
            with _traced_rng(jax.random.wrap_key_data(rng_key)):
                out = self.fn(*_as_tensor_tree(args),
                              **_as_tensor_tree(kwargs))
            new_buffers = {k: b._value
                           for k, b in self.named_closure_buffers().items()}
            return _as_array_tree(out), new_buffers
        finally:
            _random._trace_state.managed_buffers = saved_managed
            for lay, (sp, sb) in zip(layers, saved):
                lay.load_raw_state(sp, sb)

    def __call__(self, params, buffers, args, kwargs, rng_key):
        layer = self.layer
        if layer is None:
            if self.closure_layers:
                return self._call_fn_mode(params, buffers, args, kwargs,
                                          rng_key)
            with _traced_rng(jax.random.wrap_key_data(rng_key)):
                out = self.fn(*_as_tensor_tree(args), **_as_tensor_tree(kwargs))
            return _as_array_tree(out), {}
        with _swap_lock:
            return self._call_layer_locked(params, buffers, args, kwargs,
                                           rng_key)

    def _call_layer_locked(self, params, buffers, args, kwargs, rng_key):
        layer = self.layer
        saved_p = {k: p._value for k, p in layer.named_parameters()}
        buffer_objs = dict(layer.named_buffers())
        saved_b = {k: b._value for k, b in buffer_objs.items()}
        saved_managed = _random._trace_state.managed_buffers
        try:
            layer.load_raw_state(params, buffers)
            # these buffers are captured below and restored in finally, so
            # forward-state writes (BN running stats) may hold tracers
            _random._trace_state.managed_buffers = saved_managed | {
                id(b) for b in buffer_objs.values()}
            with _traced_rng(jax.random.wrap_key_data(rng_key)):
                out = layer(*_as_tensor_tree(args), **_as_tensor_tree(kwargs))
            new_buffers = {k: b._value for k, b in layer.named_buffers()}
            return _as_array_tree(out), new_buffers
        finally:
            _random._trace_state.managed_buffers = saved_managed
            layer.load_raw_state(saved_p, saved_b)


_TRACE_BREAKS = (jax.errors.ConcretizationTypeError,
                 jax.errors.TracerArrayConversionError,
                 jax.errors.TracerBoolConversionError,
                 jax.errors.TracerIntegerConversionError,
                 TracedConcretizationError)


class _GraphBreak(Exception):
    """Internal: a trace failed for one call signature; carries the cache
    key so the fallback stays per-signature."""

    def __init__(self, key, cause):
        super().__init__(str(cause))
        self.key = key
        self.cause = cause


class StaticFunction:
    """Returned by ``to_static``: runs the traced, XLA-compiled whole-graph
    program while still composing with eager autograd."""

    def __init__(self, fn_or_layer, input_spec=None, full_graph=True, backend=None):
        from ..nn import Layer

        if isinstance(fn_or_layer, Layer):
            self._layer, self._fn = fn_or_layer, None
        else:
            self._layer, self._fn = None, fn_or_layer
        self._functional = _FunctionalModel(self._layer, self._fn)
        # One compiled executable per (training mode, arg tree, static leaves);
        # jax.jit adds shape/dtype specialization beneath this.
        self._compiled: dict = {}
        # full_graph=False: the reference's SOT route splits at untraceable
        # points and keeps the surrounding segments compiled
        # (python/paddle/jit/sot/). Value-level translation = guarded
        # speculation (core/speculation.py): a signature that breaks is
        # ground-truthed eagerly ONCE (concretization outcomes recorded),
        # then recompiled with the outcomes baked + guard predicates as
        # extra outputs; later calls run the compiled specialization and
        # validate the guards, re-recording on mismatch. The matmul
        # prefix AND suffix around a data-dependent Python branch both run
        # from the compiled program.
        self._full_graph = bool(full_graph)
        self._guarded: dict = {}   # sig key -> {"last": [outcomes] | None}

    def _get_compiled(self, key, tree, static_leaves, n_leaves,
                      outcomes=None):
        from ..core import speculation as _spec

        cache_key = (key if outcomes is None
                     else (key, _spec.freeze_outcomes(outcomes)))
        fn = self._compiled.get(cache_key)
        if fn is not None:
            return fn
        functional = self._functional

        def pure(params, buffers, dyn, rng_key):
            flat = [
                dyn[i] if i in dyn else static_leaves[i] for i in range(n_leaves)
            ]
            a, kw = jax.tree_util.tree_unflatten(tree, flat)
            if outcomes is None:
                out, new_bufs = functional(params, buffers, a, kw, rng_key)
                return out, new_bufs, []
            # speculation replay: concretizations bake the recorded
            # outcomes; their source tensors ride out as guard predicates
            # in their ORIGINAL dtypes (an f32 round-trip would alias
            # integer guards >= 2^24)
            with _spec.replaying(outcomes) as rs:
                out, new_bufs = functional(params, buffers, a, kw, rng_key)
                preds = [jnp.asarray(p) for p in rs.preds]
            return out, new_bufs, preds

        fn = jax.jit(pure)
        self._compiled[cache_key] = fn
        return fn

    def __call__(self, *args, **kwargs):
        try:
            return self._call_traced(args, kwargs)
        except _GraphBreak as gb:
            e = gb.cause
            if self._full_graph:
                raise RuntimeError(
                    "to_static(full_graph=True) could not trace this "
                    "function (data-dependent Python control flow); use "
                    "jit.cond/while_loop/scan inside the graph, or pass "
                    "full_graph=False to fall back to eager") from e
            import warnings

            warnings.warn(
                f"to_static: graph break ({type(e).__name__}); this call "
                "signature switches to guarded speculation (compiled "
                "program + guard validation; other signatures stay fully "
                "compiled)")
            self._guarded.setdefault(gb.key, {"last": None})
            return self._record_and_run(gb.key, args, kwargs)

    def _run_eager(self, args, kwargs):
        if self._layer is not None:
            return self._layer(*args, **kwargs)
        return self._fn(*args, **kwargs)

    def _record_and_run(self, key, args, kwargs):
        """Ground-truth phase: run eagerly, recording every concretization
        outcome; the next call compiles the guarded specialization."""
        from ..core import speculation as _spec

        with _spec.recording() as rec:
            result = self._run_eager(args, kwargs)
        self._guarded[key]["last"] = list(rec.recorded)
        return result

    # consecutive mis-speculations before a signature retires to eager
    # (an unstable or rounding-flapping guard would otherwise pay compiled
    # + eager on every call)
    _MAX_MISSPECULATIONS = 3

    def _call_guarded(self, key, args, kwargs):
        """Run the compiled specialization for this signature's last
        recorded outcomes and validate its guard predicates; on mismatch
        (or a novel break) re-ground-truth eagerly. Side effects (buffer
        writes) are deferred until the guards validate, so a
        mis-speculated run leaves no state behind."""
        from ..core import speculation as _spec

        st = self._guarded[key]
        if st.get("retired"):
            return self._run_eager(args, kwargs)
        outcomes = st["last"]
        if outcomes is not None:
            try:
                result, pred_vals, new_buffers = self._call_traced(
                    args, kwargs, outcomes=outcomes)
            except _GraphBreak:
                return self._record_and_run(key, args, kwargs)
            if _spec.outcomes_match(pred_vals, outcomes):
                st["misses"] = 0
                self._write_buffers(new_buffers)
                return result
            st["misses"] = st.get("misses", 0) + 1
            if st["misses"] >= self._MAX_MISSPECULATIONS:
                import warnings

                warnings.warn(
                    "to_static: speculation guards flapped "
                    f"{st['misses']}x for one call signature; retiring it "
                    "to eager execution")
                st["retired"] = True
        return self._record_and_run(key, args, kwargs)

    def _call_traced(self, args, kwargs, outcomes=None):
        layer = self._layer
        if layer is not None:
            param_objs = dict(layer.named_parameters())
            params = {k: p._value for k, p in param_objs.items()}
            buffers = {k: b._value for k, b in layer.named_buffers()}
            training = layer.training
        else:
            # plain function: re-resolve closure-captured Layers at CALL
            # time (globals may be assigned/swapped after decoration);
            # their params ride as pytree inputs so optimizer updates
            # don't recompile and gradients flow (reference: dy2static
            # trains decorated fns)
            self._functional.closure_layers = _closure_layers(self._fn)
            if self._functional.closure_layers:
                param_objs = self._functional.named_closure_params()
                params = {k: p._value for k, p in param_objs.items()}
                buffers = {k: b._value
                           for k, b in
                           self._functional.named_closure_buffers().items()}
                # per-layer flags: different train/eval combinations must
                # not share a compiled program
                training = tuple(lay.training
                                 for lay in self._functional.closure_layers)
            else:
                param_objs, params, buffers, training = {}, {}, {}, False

        flat, tree = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_IS_TENSOR)
        dyn: dict[int, jax.Array] = {}
        diff_pos: list[int] = []
        diff_tensors: list[Tensor] = []
        static_leaves: dict[int, object] = {}
        for i, v in enumerate(flat):
            if isinstance(v, Tensor):
                dyn[i] = v._value
                if not v.stop_gradient:
                    diff_pos.append(i)
                    diff_tensors.append(v)
            elif isinstance(v, (jax.Array, np.ndarray)):
                dyn[i] = jnp.asarray(v)
            else:
                static_leaves[i] = v

        key = (training, tree, _freeze(static_leaves))
        if outcomes is None and key in self._guarded:
            return self._call_guarded(key, args, kwargs)
        compiled = self._get_compiled(key, tree, static_leaves, len(flat),
                                      outcomes=outcomes)
        rng_key = jax.random.key_data(_random.next_key())

        diff_params = {
            k: p for k, p in param_objs.items()
            if p.trainable and not p.stop_gradient
        }
        needs_grad = autograd.is_grad_enabled() and (diff_params or diff_tensors)

        try:
            if not needs_grad:
                out, new_buffers, preds = compiled(params, buffers, dyn,
                                                   rng_key)
                result = _as_tensor_tree(out)
                if outcomes is not None:
                    # buffer writes deferred: _call_guarded applies them
                    # only after the guards validate
                    return (result, [np.asarray(p) for p in preds],
                            new_buffers)
                self._write_buffers(new_buffers)
                return result

            frozen = {k: v for k, v in params.items() if k not in diff_params}

            def fwd(p_diff, diff_vals):
                full = dict(frozen)
                full.update(p_diff)
                dyn2 = dict(dyn)
                for pos, val in zip(diff_pos, diff_vals):
                    dyn2[pos] = val
                return compiled(full, buffers, dyn2, rng_key)

            (out, new_buffers, preds), vjp_fn = jax.vjp(
                fwd,
                {k: p._value for k, p in diff_params.items()},
                [t._value for t in diff_tensors],
            )
        except _TRACE_BREAKS as e:
            from ..core import speculation as _spec

            cache_key = (key if outcomes is None
                         else (key, _spec.freeze_outcomes(outcomes)))
            self._compiled.pop(cache_key, None)  # drop half-traced program
            raise _GraphBreak(key, e) from e
        if outcomes is None:  # speculative runs defer until guards validate
            self._write_buffers(new_buffers)

        out_flat, out_tree = jax.tree_util.tree_flatten(out)
        edge_tensors = list(diff_params.values()) + diff_tensors
        edges = [t._grad_edge() for t in edge_tensors]
        param_names = list(diff_params)
        out_shapes = [(v.shape, v.dtype) for v in out_flat]
        zero_buf_cot = jax.tree_util.tree_map(jnp.zeros_like, new_buffers)
        # integer/bool predicates take float0 cotangents (jax's symbolic
        # zero for non-differentiable outputs)
        zero_pred_cot = [
            jnp.zeros_like(p) if jnp.issubdtype(p.dtype, jnp.inexact)
            else np.zeros(p.shape, jax.dtypes.float0) for p in preds
        ]

        def backward_fn(grad_outputs, _vjp=vjp_fn):
            gflat = [
                g if g is not None else jnp.zeros(s, d)
                for g, (s, d) in zip(grad_outputs, out_shapes)
            ]
            gout = jax.tree_util.tree_unflatten(out_tree, gflat)
            gp, gt = _vjp((gout, zero_buf_cot, zero_pred_cot))
            return tuple([gp[k] for k in param_names] + list(gt))

        node = GradNode("to_static", backward_fn, edges, len(out_flat),
                        tuple(True for _ in edges))
        out_tensors = []
        for i, v in enumerate(out_flat):
            t = Tensor._from_value(v)
            if jnp.issubdtype(v.dtype, jnp.inexact):
                t.stop_gradient = False
                t._grad_node = node
                t._grad_slot = i
            out_tensors.append(t)
        result = jax.tree_util.tree_unflatten(out_tree, out_tensors)
        if outcomes is not None:
            return result, [np.asarray(p) for p in preds], new_buffers
        return result

    def _write_buffers(self, new_buffers):
        if not new_buffers:
            return
        if self._layer is not None:
            bindex = dict(self._layer.named_buffers())
        elif self._functional.closure_layers:
            bindex = self._functional.named_closure_buffers()
        else:
            return
        for k, v in new_buffers.items():
            if k in bindex and not isinstance(v, jax.core.Tracer):
                bindex[k]._value = v


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, **kwargs):
    """Compile a Layer or function into a whole-graph XLA program.

    Reference API: python/paddle/jit/api.py ``paddle.jit.to_static``::

        model = paddle.jit.to_static(model)   # Layer -> compiled proxy
        @paddle.jit.to_static                 # or decorate a function
        def f(x): ...
    """
    if function is None:
        return lambda f: to_static(f, input_spec=input_spec, full_graph=full_graph)
    from ..nn import Layer

    static_fn = StaticFunction(function, input_spec=input_spec, full_graph=full_graph)
    if isinstance(function, Layer):
        return _StaticLayerProxy(function, static_fn)
    functools.update_wrapper(static_fn, function)
    return static_fn


class _StaticLayerProxy:
    """Layer-like proxy whose __call__ is compiled; everything else
    (state_dict, parameters, train/eval, attribute access) delegates to the
    wrapped Layer — the analog of the reference's TranslatedLayer."""

    def __init__(self, layer, static_fn):
        object.__setattr__(self, "_layer", layer)
        object.__setattr__(self, "_static_fn", static_fn)

    def __call__(self, *args, **kwargs):
        return self._static_fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_layer"), name)

    def __setattr__(self, name, value):
        setattr(object.__getattribute__(self, "_layer"), name, value)

    def __repr__(self):
        return f"ToStatic({object.__getattribute__(self, '_layer')!r})"


# ------------------------------------------------------------ TrainStep

class TrainStep:
    """ONE compiled XLA program for forward + backward + optimizer update.

    TPU-native replacement for the reference's static-graph training
    executors (SURVEY.md §2.4): parameters, optimizer accumulators and master
    weights are donated pytree inputs; the loss gradient comes from
    ``jax.grad`` inside the trace; the optimizer's functional update runs in
    the same program so XLA fuses the whole step into one executable launch.

    Usage::

        step = TrainStep(model, loss_fn, optimizer)
        for x, y in loader:
            # labels ride as traced operands; loss_fn receives (*outputs, y)
            loss = step(x, labels=y)   # state updated in place
    """

    def __init__(self, model, loss_fn, optimizer):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._functional = _FunctionalModel(model)
        params = dict(model.named_parameters())
        optimizer.register_param_names(params)
        self._trainable = {k for k, p in params.items() if p.trainable}
        named = {k: p._value for k, p in params.items() if k in self._trainable}
        self._accs, self._masters = optimizer.init_functional_state(named)
        # Static per-param clip exemptions for the functional clip call
        # (Parameter objects don't exist inside the trace).
        self._clip_attrs = {
            k: type("P", (), {"need_clip": getattr(p, "need_clip", True)})()
            for k, p in params.items()
        }
        self._compiled = None
        # scanned multi-step program; jax.jit's cache keys on the rng-key
        # operand shape (N, ...), so different `steps` values coexist
        self._multi = None

    def _one_step_fn(self):
        functional = self._functional
        optimizer = self.optimizer
        loss_fn = self.loss_fn
        trainable = self._trainable
        clip_attrs = self._clip_attrs
        has_clip = (optimizer._grad_clip is not None
                    or bool(optimizer._group_clip))

        def clip_grads(grads):
            # partition by EFFECTIVE clip (param groups may override the
            # optimizer clip); each clip sees only its own grads, so a
            # group-local global norm stays group-local
            out = dict(grads)
            for c, names in optimizer._partition_by_clip(
                    list(grads), optimizer._clip_by_name,
                    optimizer._group_of_by_name):
                clipped = c._clip_arrays(
                    [grads[k] for k in names], [clip_attrs[k] for k in names])
                out.update(zip(names, clipped))
            return out

        def one_step(params, buffers, accs, masters, lr, t, rng_key, args,
                     kwargs, labels):
            p_train = {k: v for k, v in params.items() if k in trainable}
            p_frozen = {k: v for k, v in params.items() if k not in trainable}

            def loss_of(p_t):
                full = dict(p_frozen)
                full.update(p_t)
                out, new_bufs = functional(full, buffers, args, kwargs, rng_key)
                out_t = (
                    tuple(Tensor._from_value(o) for o in out)
                    if isinstance(out, tuple)
                    else Tensor._from_value(out)
                )
                outs = out_t if isinstance(out_t, tuple) else (out_t,)
                if labels is not None:
                    # labels ride as traced operands — closure-captured
                    # labels would be baked into the executable as constants
                    lab = jax.tree_util.tree_map(
                        Tensor._from_value, labels)
                    loss = loss_fn(*outs, lab)
                else:
                    loss = loss_fn(*outs)
                loss_val = loss._value if isinstance(loss, Tensor) else loss
                return loss_val, new_bufs

            (loss_val, new_buffers), grads = jax.value_and_grad(
                loss_of, has_aux=True
            )(p_train)

            if getattr(optimizer, "_master_grad", False):
                # fp32 grads before clip/update (amp master_grad semantics)
                with jax.named_scope("cast"):
                    grads = {k: g.astype(jnp.float32)
                             for k, g in grads.items()}
            if has_clip:
                with jax.named_scope("clip"):
                    grads = clip_grads(grads)

            with jax.named_scope("optimizer"):
                new_p, new_accs, new_masters = optimizer.functional_update(
                    p_train, grads, accs, masters, lr, t
                )
            out_params = dict(p_frozen)
            out_params.update(new_p)
            return loss_val, out_params, new_buffers, new_accs, new_masters

        return one_step

    def _build(self):
        return jax.jit(self._one_step_fn(), donate_argnums=(0, 2, 3))

    def _build_multi(self):
        """N whole train steps chained by lax.scan inside ONE donated
        program — the multi-step product path. Per-step RNG keys ride as a
        scanned (N, ...) operand drawn from the host stream, so stochastic
        models reproduce N sequential ``__call__``s exactly; lr is held for
        the scanned window since schedulers step on host."""
        one_step = self._one_step_fn()

        def many(params, buffers, accs, masters, lr, t0, rng_keys, args,
                 kwargs, labels):
            def body(carry, it):
                i, key_i = it
                params, buffers, accs, masters = carry
                loss, params, buffers, accs, masters = one_step(
                    params, buffers, accs, masters, lr, t0 + i, key_i,
                    args, kwargs, labels)
                return (params, buffers, accs, masters), loss

            n = rng_keys.shape[0]
            (params, buffers, accs, masters), losses = jax.lax.scan(
                body, (params, buffers, accs, masters),
                (jnp.arange(n, dtype=jnp.int32), rng_keys))
            return losses, params, buffers, accs, masters

        return jax.jit(many, donate_argnums=(0, 2, 3))

    def run(self, *args, steps, labels=None, **kwargs):
        """Run ``steps`` full train steps as ONE compiled dispatch; returns
        the per-step losses (shape (steps,)). State — parameters, buffers,
        optimizer accumulators, step count, AND the host RNG stream — lands
        exactly as after ``steps`` sequential ``__call__``s."""
        if self._multi is None:
            self._multi = self._build_multi()
        model, optimizer = self.model, self.optimizer
        params = {k: p._value for k, p in model.named_parameters()}
        buffers = {k: b._value for k, b in model.named_buffers()}
        lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        t0 = jnp.asarray(optimizer._step_count + 1, jnp.int32)
        rng_keys = jnp.stack([
            jax.random.key_data(_random.next_key())
            for _ in range(int(steps))
        ])
        losses, new_params, new_buffers, self._accs, self._masters = \
            self._multi(params, buffers, self._accs, self._masters, lr,
                        t0, rng_keys, _as_array_tree(args),
                        _as_array_tree(kwargs), _as_array_tree(labels))
        optimizer._step_count += int(steps)
        model.load_raw_state(new_params, new_buffers)
        return Tensor._from_value(losses)

    def _step_operands(self, t, args, kwargs, labels):
        """The one-step program's operand tuple at step count ``t``."""
        model, optimizer = self.model, self.optimizer
        params = {k: p._value for k, p in model.named_parameters()}
        buffers = {k: b._value for k, b in model.named_buffers()}
        lr = jnp.asarray(optimizer.get_lr(), jnp.float32)
        rng_key = jax.random.key_data(_random.next_key())
        return (params, buffers, self._accs, self._masters, lr,
                jnp.asarray(t, jnp.int32), rng_key, _as_array_tree(args),
                _as_array_tree(kwargs), _as_array_tree(labels))

    def lower(self, *args, labels=None, **kwargs):
        """``jax.jit(...).lower`` of the one-step program for these
        inputs — the ``jax.stages.Lowered`` whose ``as_text()`` /
        ``compile()`` show what a ``__call__`` with the same inputs
        runs (kernels present, memory). Touches no training state
        beyond drawing one key from the host RNG stream."""
        if self._compiled is None:
            self._compiled = self._build()
        return self._compiled.lower(*self._step_operands(
            self.optimizer._step_count + 1, args, kwargs, labels))

    def __call__(self, *args, labels=None, **kwargs):
        # host time to issue one step (the program runs on asynchronously);
        # a call that traced and compiled says so
        with annotate("train.step_dispatch") as sp:
            if self._compiled is None:
                self._compiled = self._build()
            traced = self._compiled._cache_size()
            self.optimizer._step_count += 1
            operands = self._step_operands(
                self.optimizer._step_count, args, kwargs, labels)
            loss, new_params, new_buffers, self._accs, self._masters = \
                self._compiled(*operands)
            self.model.load_raw_state(new_params, new_buffers)
            compiled = self._compiled._cache_size() != traced
            sp.set(step=self.optimizer._step_count, compiled=compiled)
            if compiled:
                self._note_program(operands)
        return Tensor._from_value(loss)

    def _note_program(self, operands):
        """File the step that the call just compiled in the profiler's
        program table (which scope and pass each of its instructions came
        from). The donated operands are gone but their shapes are not;
        lowering from those comes out of JAX's in-process cache, so nothing
        is compiled a second time (``tests/test_program_ops.py`` holds
        that), and only a call that compiled comes here."""
        def aval(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None)

        note_program("one_step", self._compiled.lower(
            *jax.tree_util.tree_map(aval, operands)).compile())

    def state_dict(self):
        """Optimizer accumulator state for checkpointing the compiled path.
        Copies the arrays — the live buffers are donated on the next step."""
        out = {k: jnp.copy(v) for k, v in self._accs.items()}
        out.update({f"master@{k}": jnp.copy(v) for k, v in self._masters.items()})
        out["@step_count"] = self.optimizer._step_count
        return out

    def set_state_dict(self, state):
        accs, masters = {}, {}
        for k, v in state.items():
            if k == "@step_count":
                self.optimizer._step_count = int(v)
            elif k.startswith("master@"):
                masters[k[len("master@"):]] = getattr(v, "_value", v)
            else:
                accs[k] = getattr(v, "_value", v)
        self._accs, self._masters = accs, masters


# ------------------------------------------------------------ control flow

def cond(pred, true_fn, false_fn, *operands):
    """Structured conditional (reference paddle.static.nn.cond / PIR IfOp,
    paddle/fluid/pir/dialect/operator/ir/control_flow_op.h:27) →
    ``lax.cond``: both branches traced, selected at run time."""
    pv = pred._value if isinstance(pred, Tensor) else pred
    ops = _as_array_tree(operands)
    out = jax.lax.cond(
        pv,
        lambda o: _as_array_tree(true_fn(*_as_tensor_tree(o))),
        lambda o: _as_array_tree(false_fn(*_as_tensor_tree(o))),
        ops,
    )
    return _as_tensor_tree(out)


def while_loop(cond_fn, body_fn, loop_vars):
    """Reference paddle.static.nn.while_loop (WhileOp) → ``lax.while_loop``."""
    init = _as_array_tree(tuple(loop_vars))
    out = jax.lax.while_loop(
        lambda vs: (lambda r: r._value if isinstance(r, Tensor) else r)(
            cond_fn(*_as_tensor_tree(vs))
        ),
        lambda vs: _as_array_tree(tuple(body_fn(*_as_tensor_tree(vs)))),
        init,
    )
    return list(_as_tensor_tree(out))


def scan(f, init, xs):
    """``lax.scan`` surface for compiler-friendly loops over a leading axis
    (the TPU-idiomatic replacement for python loops in traced code)."""
    carry, ys = jax.lax.scan(
        lambda c, x: tuple(
            _as_array_tree(f(_as_tensor_tree(c), _as_tensor_tree(x)))
        ),
        _as_array_tree(init),
        _as_array_tree(xs),
    )
    return _as_tensor_tree(carry), _as_tensor_tree(ys)


def ignore_module(modules):  # reference-compat no-op (we trace values, not code)
    return None


def not_to_static(fn):
    """reference-compat marker; tracing follows values so this is advisory."""
    fn.__jit_not_to_static__ = True
    return fn

from .serialization import (  # noqa: E402,F401
    TranslatedLayer,
    load,
    save,
    save_generate,
)

__all__ += ["save", "load", "save_generate", "TranslatedLayer"]

from .compile_watch import (  # noqa: E402,F401
    BACKEND_COMPILE_EVENT,
    CompileWatchdog,
    compile_watchdog,
    count_backend_compiles,
)

__all__ += ["CompileWatchdog", "compile_watchdog",
            "count_backend_compiles", "BACKEND_COMPILE_EVENT"]

# every compile of the process is counted and recorded (xla.compile
# spans), a model's build and a train step's included: set-up time is an
# end-to-end metric and this is what attributes it
compile_watchdog().start()


# ---- namespace parity tail (reference python/paddle/jit/__init__.py)

_to_static_enabled = True


def enable_to_static(enable):
    """Reference jit.enable_to_static: globally toggle to_static tracing
    (StaticFunction falls back to eager when disabled)."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)


def set_code_level(level=100, also_to_stdout=False):
    """Reference sot/dy2static transformed-code logging. The TPU build's
    trace artifact is the jaxpr/StableHLO, inspectable via
    jax.make_jaxpr / serialization.save — this knob is accepted and
    recorded for parity."""
    import logging

    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level else logging.WARNING)


def set_verbosity(level=0, also_to_stdout=False):
    """Reference jit.set_verbosity over the dy2static logger."""
    import logging

    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level else logging.WARNING)


__all__ += ["enable_to_static", "set_code_level", "set_verbosity"]
