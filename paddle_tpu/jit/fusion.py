"""Elementwise-chain fusion pass over traced programs (ISSUE 20).

Decode is memory-bandwidth-bound: every standalone elementwise launch
re-reads its activations HBM<->VMEM for free work. XLA already fuses
most producer->consumer elementwise chains, but the decision is made
per-HLO-module with fusion heuristics that the serving segment program
(scan body with donated cache buffers) does not always win. This pass
makes the grouping EXPLICIT at the jaxpr level: maximal runs of
producer->consumer elementwise equations (bias/residual adds,
activations, scales, casts, clamps) are outlined into a single
``closed_call`` equation each, so the lowered program presents one
fusion-island per chain instead of a kernel zoo.

Semantics are preserved EXACTLY: the outlined chain evaluates the very
same primitive equations in the same order — ``closed_call`` is a pure
grouping construct, so fused and unfused programs are bit-identical
(the serving engine's fused-vs-unfused token-stream contract rides on
this).

The pass recurses into higher-order equations (``scan`` bodies,
``while`` cond/body, ``cond`` branches, ``pjit``/``closed_call``
sub-jaxprs), which is where the serving segment program keeps its whole
decode body — but never into a ``pallas_call``'s kernel body.

``count_eqns``/``fusion_stats`` expose the equation counts before and
after — the op-bench ``decode_layer_launches`` reading.
"""
from __future__ import annotations

import functools

import jax
from jax import core as _jcore
from jax import tree_util
from jax.extend import core
from jax.extend.core.primitives import closed_call_p

__all__ = ["fuse_elementwise_chains", "rewrite_closed_jaxpr",
           "fusion_stats", "count_eqns", "ELEMENTWISE_PRIMS"]

# Primitive names (lax *_p .name) that read/write each element exactly
# once — safe to outline and profitable to co-schedule. broadcast_in_dim
# and convert_element_type are shape/dtype glue the chains are built
# through; select_n is the where() workhorse of masked decode updates.
ELEMENTWISE_PRIMS = frozenset([
    "add", "sub", "mul", "div", "rem", "neg", "sign", "abs",
    "exp", "exp2", "expm1", "log", "log1p", "tanh", "logistic",
    "sqrt", "rsqrt", "cbrt", "square", "pow", "integer_pow",
    "max", "min", "clamp", "floor", "ceil", "round", "erf", "erfc",
    "is_finite", "nextafter",
    "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic",
    "eq", "ne", "ge", "gt", "le", "lt",
    "select_n", "convert_element_type", "broadcast_in_dim",
])

# eqn params under which sub-jaxprs hide (scan/while/cond/pjit/call)
_SUBJAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                    "branches")


def _outvars(eqn):
    return [v for v in eqn.outvars if not isinstance(v, _jcore.DropVar)]


def _rewrite_sub(value, stats):
    if isinstance(value, core.ClosedJaxpr):
        return core.ClosedJaxpr(_rewrite_jaxpr(value.jaxpr, stats),
                                value.consts)
    if isinstance(value, core.Jaxpr):
        return _rewrite_jaxpr(value, stats)
    if isinstance(value, (tuple, list)):
        items = [_rewrite_sub(v, stats) for v in value]
        return type(value)(items)
    return value


def _rewrite_jaxpr(jaxpr, stats):
    # recurse into higher-order equations first, then partition this level
    eqns = []
    for eqn in jaxpr.eqns:
        new_params = None
        # a Pallas kernel body is Mosaic's to schedule, and the Pallas
        # TPU lowering has no rule for closed_call: leave it untouched
        sub_params = (() if eqn.primitive.name == "pallas_call"
                      else _SUBJAXPR_PARAMS)
        for k in sub_params:
            if k in eqn.params:
                v = eqn.params[k]
                rv = _rewrite_sub(v, stats)
                if rv is not v:
                    if new_params is None:
                        new_params = dict(eqn.params)
                    new_params[k] = rv
        if new_params is not None:
            eqn = eqn.replace(params=new_params)
        eqns.append(eqn)

    out_eqns = []
    n = len(eqns)
    i = 0
    while i < n:
        eqn = eqns[i]
        if eqn.primitive.name not in ELEMENTWISE_PRIMS or eqn.effects:
            out_eqns.append(eqn)
            i += 1
            continue
        # grow a maximal producer->consumer run: each appended equation
        # must consume at least one value defined inside the chain
        chain = [eqn]
        defined = set(_outvars(eqn))
        j = i + 1
        while j < n:
            nxt = eqns[j]
            if nxt.primitive.name not in ELEMENTWISE_PRIMS or nxt.effects:
                break
            if not any(isinstance(v, core.Var) and v in defined
                       for v in nxt.invars):
                break
            chain.append(nxt)
            defined.update(_outvars(nxt))
            j += 1
        if len(chain) < 2:
            out_eqns.append(eqn)
            i += 1
            continue
        # chain interface: external inputs in first-use order; outputs =
        # chain-defined values still live past the chain
        ext, seen = [], set()
        for e in chain:
            for v in e.invars:
                if (isinstance(v, core.Var) and v not in defined
                        and v not in seen):
                    seen.add(v)
                    ext.append(v)
        live = set(v for v in jaxpr.outvars if isinstance(v, core.Var))
        for e in eqns[j:]:
            live.update(v for v in e.invars if isinstance(v, core.Var))
        outv = [v for e in chain for v in _outvars(e) if v in live]
        if not outv:
            out_eqns.extend(chain)
            i = j
            continue
        inner = core.Jaxpr(
            (), list(ext), list(outv), list(chain),
            debug_info=_jcore.DebugInfo(
                "elementwise_chain", jaxpr.debug_info.func_src_info,
                (None,) * len(ext), (None,) * len(outv)))
        out_eqns.append(_jcore.new_jaxpr_eqn(
            list(ext), list(outv), closed_call_p,
            dict(call_jaxpr=core.ClosedJaxpr(inner, ())),
            _jcore.no_effects, chain[0].source_info))
        stats["chains"] += 1
        stats["collapsed_eqns"] += len(chain)
        i = j
    return jaxpr.replace(eqns=out_eqns)


def count_eqns(jaxpr):
    """Total equation count, recursing into sub-jaxprs (the launch-site
    proxy the op bench records as ``decode_layer_launches``)."""
    if isinstance(jaxpr, core.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    total = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for k in _SUBJAXPR_PARAMS:
            v = eqn.params.get(k)
            if isinstance(v, (core.Jaxpr, core.ClosedJaxpr)):
                total += count_eqns(v)
            elif isinstance(v, (tuple, list)):
                total += sum(count_eqns(b) for b in v
                             if isinstance(b, (core.Jaxpr, core.ClosedJaxpr)))
    return total


def rewrite_closed_jaxpr(closed):
    """Rewrite a ClosedJaxpr, collapsing elementwise chains into
    ``closed_call`` groups. Returns ``(rewritten, stats)``; on any
    rewrite failure the ORIGINAL jaxpr comes back with
    ``stats["error"]`` set — fusion is an optimization, never a
    correctness dependency."""
    stats = {"chains": 0, "collapsed_eqns": 0,
             "eqns_before": count_eqns(closed)}
    try:
        rewritten = core.ClosedJaxpr(_rewrite_jaxpr(closed.jaxpr, stats),
                                     closed.consts)
    except Exception as e:  # pragma: no cover - defensive
        stats["error"] = f"{type(e).__name__}: {e}"
        stats["eqns_after"] = stats["eqns_before"]
        return closed, stats
    stats["eqns_after"] = count_eqns(rewritten)
    return rewritten, stats


def fuse_elementwise_chains(fn):
    """Wrap ``fn`` so its traced program has elementwise chains collapsed.

    The wrapper is signature-preserving over positional pytree args, so
    ``jax.jit(fuse_elementwise_chains(f), donate_argnums=...)`` keeps
    donation and AOT ``lower().compile()`` working unchanged. Outputs
    are bit-identical to ``fn``'s: the same primitive equations run in
    the same order, merely grouped.
    """
    @functools.wraps(fn)
    def wrapped(*args):
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        fused, _ = rewrite_closed_jaxpr(closed)
        flat, _ = tree_util.tree_flatten(args)
        outs = core.jaxpr_as_fun(fused)(*flat)
        return tree_util.tree_unflatten(
            tree_util.tree_structure(out_shape), outs)
    return wrapped


def fusion_stats(fn, *args):
    """Trace ``fn`` on ``args`` and report what the pass would do:
    ``{eqns_before, eqns_after, chains, collapsed_eqns}``."""
    closed = jax.make_jaxpr(fn)(*args)
    _, stats = rewrite_closed_jaxpr(closed)
    return stats
