"""Mixture-of-Experts layer with GShard-style capacity dispatch.

Analog of /root/reference/python/paddle/incubate/distributed/models/moe/
moe_layer.py:263 (``MoELayer``) and its gates (gate/naive_gate.py,
switch_gate.py, gshard_gate.py), plus the global_scatter/global_gather
collective ops used for expert-parallel dispatch.

TPU-native dispatch: index-based scatter-add into the (E*C) slot space and
a weighted gather back (the global_scatter/global_gather shapes) — O(T*K)
routing state, never a dense (T, E, C) combine tensor.

Expert parallelism: with stacked experts on an ``ep`` mesh axis, the
forward runs an EXPLICIT shard_map EP exchange — tokens sharded over
``ep``, each device dispatches its local tokens into per-(rank, expert)
capacity slots, ``lax.all_to_all`` moves the slots to the experts' owners
and back (the literal global_scatter/global_gather pair,
moe_layer.py:263) — so dispatch bandwidth stays at T*D/ep instead of the
full all-gather GSPMD falls back to when left to propagate the scatter on
its own (verified by HLO inspection in tests/test_fleet.py).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ...core.tensor import Tensor
from ...nn.layer_base import Layer
from ...nn.layers_common import LayerList
from ...ops import registry as _registry

__all__ = ["MoELayer", "NaiveGate", "SwitchGate", "GShardGate"]


def _moe_dispatch_kernel(x, gate_logits, capacity, top_k):
    """tokens (T, D) + logits (T, E) -> dispatched (E, C, D), routing
    indices (K, T) into the flattened (E*C) slot space (-1 = dropped),
    routing weights (K, T), aux load-balance loss.

    Index-based formulation (the reference's global_scatter shape): the
    dispatch is a scatter-add into E*C slots and the combine a gather —
    O(T*K) routing state instead of the dense (T, E, C) one-hot combine
    tensor of the GShard-einsum formulation, which at real scale
    (T=8192, E=64, C≈1.25T/E) is memory-hostile. Pure jnp; registered as
    an op so eager calls are jit-cached and gradients flow via jax.vjp
    (scatter-add/gather transpose to each other)."""
    import jax

    T, D = x.shape
    E = gate_logits.shape[1]
    C = capacity
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    probs = probs.astype(x.dtype)  # (T, E)

    remaining = probs
    position_in_expert = jnp.zeros((E,), jnp.int32)
    slot_rounds = []
    weight_rounds = []
    first_mask = None
    # iterative top-k with capacity (GShard top-2 when top_k=2)
    for r in range(top_k):
        idx = jnp.argmax(remaining, axis=1)                      # (T,)
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # (T, E)
        pos = jnp.cumsum(onehot, axis=0) - 1 + position_in_expert[None, :]
        pos_tok = jnp.sum(pos * onehot, axis=1)                  # (T,)
        fits = pos_tok < C
        w = jnp.sum(probs * onehot, axis=1) * fits               # (T,)
        slot = jnp.where(fits, idx * C + jnp.clip(pos_tok, 0, C - 1), -1)
        slot_rounds.append(slot.astype(jnp.int32))
        weight_rounds.append(w)
        position_in_expert = position_in_expert + jnp.sum(
            onehot * fits[:, None], axis=0)
        remaining = remaining * (1 - onehot)
        if first_mask is None:
            first_mask = onehot

    slots = jnp.stack(slot_rounds)        # (K, T)
    weights = jnp.stack(weight_rounds)    # (K, T)

    # load-balance aux loss (GShard eq.4): E * mean(frac_tokens * frac_prob)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(first_mask.astype(jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E

    # dispatch = scatter-add into the E*C slot space; dropped tokens go to
    # a discarded overflow row (no masking needed — the slice drops them,
    # and its transpose gives those tokens a zero cotangent)
    flat = jnp.zeros((E * C + 1, D), x.dtype)
    for r in range(top_k):
        tgt = jnp.where(slots[r] >= 0, slots[r], E * C)
        flat = flat.at[tgt].add(x)
    dispatched = flat[:E * C].reshape(E, C, D)
    return dispatched, slots, weights, aux


_registry.register_op(
    "moe_dispatch", _moe_dispatch_kernel, inputs=("x", "gate_logits"))


def _moe_ep_kernel(x, gate_logits, w_in, w_out, *, mesh, ep_axis, capacity,
                   top_k, activation):
    """Expert-parallel MoE forward as ONE shard_map program over ``ep``:
    local dispatch -> all_to_all (global_scatter) -> local stacked-expert
    FFN -> all_to_all (global_gather) -> local combine. ``capacity`` is
    per (source rank, expert); the per-expert total is ``ep * capacity``,
    matching the replicated kernel's global capacity."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    E = gate_logits.shape[1]
    ep = mesh.shape[ep_axis]
    E_loc = E // ep

    def body(x_loc, lg_loc, w_in_loc, w_out_loc):
        T_loc, D = x_loc.shape
        dispatched, slots, weights, aux = _moe_dispatch_kernel(
            x_loc, lg_loc, capacity, top_k)             # (E, C, D) local
        # global_scatter: destination-rank-major blocks, transposed so
        # rank r receives every source's slots for ITS experts
        d = dispatched.reshape(ep, E_loc, capacity, D)
        d = jax.lax.all_to_all(d, ep_axis, split_axis=0, concat_axis=0,
                               tiled=True)              # (ep, E_loc, C, D)
        d = d.transpose(1, 0, 2, 3).reshape(E_loc, ep * capacity, D)
        # exact-erf gelu to match the replicated path's F.gelu
        # (jax.nn.gelu defaults to the tanh approximation)
        act = {"gelu": lambda v: jax.nn.gelu(v, approximate=False)}.get(
            activation) or getattr(jax.nn, activation)
        h = act(jnp.einsum("ecd,edh->ech", d, w_in_loc))
        out_e = jnp.einsum("ech,ehd->ecd", h, w_out_loc)
        # global_gather: send each source rank its tokens' outputs back
        g = out_e.reshape(E_loc, ep, capacity, D).transpose(1, 0, 2, 3)
        g = jax.lax.all_to_all(g, ep_axis, split_axis=0, concat_axis=0,
                               tiled=True)              # (ep, E_loc, C, D)
        expert_out = g.reshape(E, capacity, D)          # global expert order
        yf = _combine_kernel(slots, weights, expert_out)
        return yf, jax.lax.pmean(aux, ep_axis)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(ep_axis), P(ep_axis), P(ep_axis), P(ep_axis)),
        out_specs=(P(ep_axis), P()),
    )(x, gate_logits, w_in, w_out)


_registry.register_op("moe_ep_forward", _moe_ep_kernel,
                      inputs=("x", "gate_logits", "w_in", "w_out"))


class NaiveGate(Layer):
    """Linear router, top-k (reference gate/naive_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, top_k=2):
        super().__init__()
        from ...nn.layers_common import Linear

        self.top_k = top_k
        self.gate = Linear(d_model, num_expert * world_size)

    def forward(self, x):
        return self.gate(x)


class SwitchGate(NaiveGate):
    """Top-1 routing (reference gate/switch_gate.py)."""

    def __init__(self, d_model, num_expert, world_size=1, top_k=1):
        super().__init__(d_model, num_expert, world_size, top_k=1)


GShardGate = NaiveGate


class MoELayer(Layer):
    """MoE block: route tokens to experts, run experts, combine.

    moe_layer.py:263 semantics: ``experts`` is a list of Layers (one per
    local expert); ``gate`` a Gate layer or config dict. Capacity factor
    bounds tokens per expert; overflow tokens contribute ZERO output (add
    a residual connection around the layer if pass-through is wanted).
    """

    def __init__(self, d_model, experts=None, gate=None, moe_group=None,
                 mp_group=None, recompute_interval=0, capacity_factor=1.25,
                 top_k=None, **kwargs):
        super().__init__()
        self.d_model = d_model
        self._stacked = None
        if isinstance(experts, (list, LayerList)):
            self.experts = (experts if isinstance(experts, LayerList)
                            else LayerList(list(experts)))
            self.num_experts = len(self.experts)
        elif isinstance(experts, Layer) and hasattr(experts, "num_experts"):
            self._stacked = experts
            self.experts = LayerList([experts])
            self.num_experts = experts.num_experts
        else:
            raise ValueError(
                "experts must be a list of Layers or a stacked-expert Layer")
        if gate is None or isinstance(gate, dict):
            cfg = gate or {}
            top = cfg.get("top_k", top_k or 2)
            typ = cfg.get("type", "naive")
            cls = SwitchGate if typ == "switch" else NaiveGate
            self.gate = cls(d_model, self.num_experts, top_k=top)
        else:
            self.gate = gate
        self.top_k = getattr(self.gate, "top_k", top_k or 2)
        self.capacity_factor = capacity_factor
        self.aux_loss = None

    def forward(self, x):
        from ...ops import reshape

        orig_shape = x.shape
        T = int(np.prod(orig_shape[:-1]))
        xf = reshape(x, [T, self.d_model])
        logits = self.gate(xf)
        capacity = max(int(self.capacity_factor * T / self.num_experts), 1)

        ep_cfg = getattr(self._stacked, "_ep", None)
        if ep_cfg is not None:
            jmesh, ep_axis = ep_cfg
            ep = jmesh.shape[ep_axis]
            if T % ep == 0 and self.num_experts % ep == 0:
                # explicit EP: per-(rank, expert) capacity, all_to_all
                # dispatch/return (global_scatter/global_gather)
                cap_loc = max(
                    int(self.capacity_factor * (T // ep) / self.num_experts),
                    1)
                yf, aux = _registry.apply_op(
                    _registry.get_op("moe_ep_forward"), xf, logits,
                    self._stacked.w_in, self._stacked.w_out,
                    mesh=jmesh, ep_axis=ep_axis, capacity=cap_loc,
                    top_k=self.top_k, activation=self._stacked.activation)
                self.aux_loss = aux
                return reshape(yf, list(orig_shape))

        dispatched, slots, weights, aux = _registry.apply_op(
            _registry.get_op("moe_dispatch"), xf, logits,
            capacity=capacity, top_k=self.top_k)
        self.aux_loss = aux

        if self._stacked is not None:
            # batched path: all experts in one einsum (ep-shardable)
            expert_out = self._stacked(dispatched)
        else:
            # per-expert loop (E small; static unroll under jit)
            outs = []
            for e, expert in enumerate(self.experts):
                outs.append(expert(dispatched[e]))
            from ...ops import stack

            expert_out = stack(outs, axis=0)  # (E, C, D)
        yf = _combine(slots, weights, expert_out)
        return reshape(yf, list(orig_shape))


def _combine_kernel(slots, weights, expert_out):
    """Gather each token's expert outputs from its (K, T) slots and weight
    them — the global_gather shape. Dropped tokens (slot -1) already carry
    weight 0, so a clipped gather suffices (no zero-row concat copy)."""
    E, C, D = expert_out.shape
    flat = expert_out.reshape(E * C, D)
    out = 0.0
    for r in range(slots.shape[0]):
        tgt = jnp.clip(slots[r], 0, E * C - 1)
        out = out + weights[r][:, None] * flat[tgt]
    return out


_registry.register_op(
    "moe_combine", _combine_kernel,
    inputs=("slots", "weights", "expert_out"))


def _combine(slots, weights, expert_out):
    return _registry.apply_op(
        _registry.get_op("moe_combine"), slots, weights, expert_out)


class StackedExpertsFFN(Layer):
    """Expert-parallel FFN with *stacked* weights: gate/up/down carry a
    leading expert dim, shardable Shard(0) over the ``ep`` mesh axis, and
    all experts run as one batched einsum — the vmap form the reference's
    fused_moe kernel implements in CUDA. Pair with MoELayer via
    ``experts=StackedExpertsFFN(...)`` (it is called with the dispatched
    (E, C, D) tensor directly)."""

    def __init__(self, num_experts, d_model, d_hidden, activation="gelu",
                 mesh=None, ep_axis="ep"):
        super().__init__()
        from ...nn import initializer as I

        self.num_experts = num_experts
        self.w_in = self.create_parameter(
            (num_experts, d_model, d_hidden),
            default_initializer=I.XavierNormal())
        self.w_out = self.create_parameter(
            (num_experts, d_hidden, d_model),
            default_initializer=I.XavierNormal())
        self.activation = activation
        self._ep = None
        if mesh is not None and ep_axis in mesh.dim_names:
            from ..api import shard_tensor
            from ..placement import Replicate, Shard

            pl = [Replicate()] * mesh.ndim
            pl[mesh.dim_names.index(ep_axis)] = Shard(0)
            shard_tensor(self.w_in, mesh, pl)
            shard_tensor(self.w_out, mesh, pl)
            self._ep = (mesh.jax_mesh(), ep_axis)

    def forward(self, dispatched):
        """(E, C, D) -> (E, C, D), one batched matmul pair over experts."""
        from ...nn import functional as F
        from ...ops import registry as _reg

        act = getattr(F, self.activation)
        h = _reg.apply_op(_reg.get_op("_moe_expert_mm"), dispatched, self.w_in)
        h = act(h)
        return _reg.apply_op(_reg.get_op("_moe_expert_mm"), h, self.w_out)


def _moe_expert_mm_kernel(x, w):
    return jnp.einsum("ecd,edh->ech", x, w)


_registry.register_op("_moe_expert_mm", _moe_expert_mm_kernel,
                      inputs=("x", "w"))
