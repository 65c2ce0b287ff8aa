"""Recompute (activation checkpointing).

Analog of /root/reference/python/paddle/distributed/fleet/recompute/
recompute.py:124 (``RecomputeFunction``: PyLayer that stows inputs + RNG
state, reruns forward during backward). Two regimes here:

* **traced** (inside jit/TrainStep): ``jax.checkpoint`` — XLA-native
  rematerialization, the mechanism the whole reference file hand-builds —
  with ONE policy for every caller: keep the five residuals the flash
  forward rule names (q, k, v, ``out`` and ``lse``,
  ``ops/pallas/flash_attention.py``), recompute everything else. In one
  sentence: *a recomputed block never rebuilds anything the attention
  kernel's backward reads.* Its backward then runs no second ``flash_fwd``
  (PR 32) and no second q / k / v product, rope or swap to ``(b, h, s, d)``
  (PR 34); it still rebuilds the two norms, the o product and the whole
  SwiGLU from the block's input. There is no parameter for it because
  there is nothing to choose: the saved set follows what the traced block
  contains. A block with no flash attention has no such names and is
  recomputed whole, as a bare ``jax.checkpoint`` would (to the lowered
  text: ``tests/test_recompute_flash_residuals.py``).

  What a caller pays is memory, in bytes a checkpointed block that holds
  one attention: tokens x hidden for the block's input (any checkpoint
  keeps that), plus tokens x (2 x q heads + 2 x kv heads) x head_dim
  elements for q, k, v and ``out``, plus ``lse`` (``f32[b, h, s, 1]``,
  which the chip pads to 128 lanes: tokens x q heads x 512 B). At
  ``internlm2-d12-pretrain-1chip``'s shape (2 x 4096 tokens, hidden 2048,
  16 / 8 heads of 128, bf16) that is 32 + 96 + 64 = 192 MiB a layer, where
  PR 32 left 128, a bare checkpoint 32 and an unrecomputed layer ~900.
  What it buys on v5e there, twelve layers a step: PERF.md section 6
  (PR 32: 525.3 -> 497.5 ms; PR 34: 498.7 -> 492.3 ms, half of what the
  products were reckoned at, because that step is compiled to the brim of
  the chip's memory and XLA rematerializes products of its own to make
  room). Nothing else joins the set by size: the SwiGLU's inputs are 128
  MiB a layer each, and a set sized to the last MiB of one cell would be a
  knob in disguise (ROADMAP S4a).
* **eager**: a GradNode that saves inputs + host RNG state; its backward
  restores the RNG, reruns ``function`` with grad enabled, and routes
  cotangents with ``autograd.grad`` — same structure as the reference's
  PyLayer backward.
"""
from __future__ import annotations

import jax

from ...core import autograd, random as _random
from ...core.autograd import GradNode
from ...core.tensor import Tensor

__all__ = ["recompute", "recompute_sequential"]


def _is_traced(values):
    return any(isinstance(v, jax.core.Tracer) for v in values)


def recompute(function, *args, **kwargs):
    preserve_rng_state = kwargs.pop("preserve_rng_state", True)
    kwargs.pop("use_reentrant", None)

    tensor_args = [a for a in args if isinstance(a, Tensor)]
    values = [t._value for t in tensor_args]

    if _is_traced(values):
        # jit path: pure-function remat over the tensor leaves
        idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]

        def pure(vals):
            call = list(args)
            for i, v in zip(idx, vals):
                call[i] = Tensor._from_value(v)
            out = function(*call, **kwargs)
            if isinstance(out, (tuple, list)):
                return tuple(o._value if isinstance(o, Tensor) else o for o in out)
            return out._value if isinstance(out, Tensor) else out

        # imported here: the Pallas stack is a quarter of a second that
        # ``import paddle_tpu`` does not otherwise pay
        from ...ops.pallas.flash_attention import FLASH_RESIDUAL_NAMES

        keep = jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUAL_NAMES)
        out_vals = jax.checkpoint(pure, policy=keep)(values)
        if isinstance(out_vals, tuple):
            return tuple(Tensor._from_value(v) for v in out_vals)
        return Tensor._from_value(out_vals)

    # Engage whenever grads are on: the block's *parameters* need their
    # grads even when no tensor input does (reference RecomputeFunction is a
    # PyLayer and always interposes).
    if not autograd.is_grad_enabled():
        return function(*args, **kwargs)

    rng_state = _random.get_rng_state() if preserve_rng_state else None
    with autograd.no_grad():
        outputs = function(*args, **kwargs)
    single = not isinstance(outputs, (tuple, list))
    out_list = [outputs] if single else list(outputs)

    diff_inputs = [t for t in tensor_args if not t.stop_gradient]
    edges = [t._grad_edge() for t in diff_inputs]
    saved_args = args

    def backward_fn(grad_outputs):
        saved_rng = _random.get_rng_state()
        if rng_state is not None:
            _random.set_rng_state(rng_state)
        try:
            # rerun with grad enabled on detached stand-ins for the inputs
            detached = []
            call = []
            for a in saved_args:
                if isinstance(a, Tensor) and not a.stop_gradient:
                    d = a.detach()
                    d.stop_gradient = False
                    detached.append(d)
                    call.append(d)
                elif isinstance(a, Tensor):
                    call.append(a.detach())
                else:
                    call.append(a)
            with autograd.enable_grad():
                re_out = function(*call, **kwargs)
            re_list = [re_out] if not isinstance(re_out, (tuple, list)) \
                else list(re_out)
            outs, gouts = [], []
            for o, g in zip(re_list, grad_outputs):
                if g is not None and isinstance(o, Tensor):
                    outs.append(o)
                    gouts.append(Tensor._from_value(g))
            # One sweep doing both jobs of the reference PyLayer backward:
            # write .grad on the leaves inside the block (parameters) AND
            # capture the gradients arriving at the detached inputs.
            capture = {}
            in_edges = []
            for d in detached:
                node, slot = d._grad_edge()
                in_edges.append((node, slot))
                if node is not None:
                    capture.setdefault((id(node), slot), [])
            autograd.backward(outs, gouts, capture=capture, write_grads=True)
            grads = []
            for node, slot in in_edges:
                vals = capture.get((id(node), slot)) if node is not None else None
                if vals:
                    g = vals[0]
                    for v in vals[1:]:
                        g = g + v
                    grads.append(g)
                else:
                    grads.append(None)
            return tuple(grads)
        finally:
            _random.set_rng_state(saved_rng)

    node = GradNode("recompute", backward_fn, edges, len(out_list),
                    tuple(True for _ in edges))
    import jax.numpy as jnp

    results = []
    for i, o in enumerate(out_list):
        if isinstance(o, Tensor) and jnp.issubdtype(o._value.dtype, jnp.inexact):
            t = Tensor._from_value(o._value)
            t.stop_gradient = False
            t._grad_node = node
            t._grad_slot = i
            results.append(t)
        else:
            results.append(o)
    return results[0] if single else tuple(results)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Segmented recompute over a Sequential (reference
    recompute_sequential): split into ``segments`` chunks, checkpoint each."""
    segments = (ctx or {}).get("segments", 1)
    if hasattr(functions, "children"):
        functions = list(functions.children())
    functions = list(functions)
    seg_size = max(len(functions) // max(segments, 1), 1)

    def make_seg(fs):
        def run(*xs):
            out = xs[0] if len(xs) == 1 else xs
            for f in fs:
                out = f(out)
            return out

        return run

    out = args[0] if len(args) == 1 else args
    for s in range(0, len(functions), seg_size):
        seg = functions[s:s + seg_size]
        out = recompute(make_seg(seg), out, **kwargs)
    return out
