"""Hybrid decoder of the ``nemotron_h`` family (NVIDIA Nemotron-H / Nemotron
3 Nano, arXiv:2504.03624): Mamba-2 layers, a few softmax-attention layers
and sparse-expert layers in ONE stack, each layer ONE mixer, on the serving
path.

Every layer is ``x += Mixer_i(rmsnorm(x))`` with the mixer named by
character i of ``hybrid_override_pattern``; then a final norm and an untied
head; no bias on any projection.

* ``M``, Mamba-2 (``H`` heads of ``P`` channels, ``G`` groups, state ``N``,
  kernel ``K``): ``[z | xBC | dt] = u W_in`` (H P | H P + 2 G N | H); ``xBC =
  silu(conv_K(xBC) + b)`` (causal, depthwise), split into ``x`` (H, P), ``B``
  and ``C`` (G, N; head h reads group h // (H / G)); ``dt = softplus(dt +
  dt_bias)``, ``a = exp(-dt exp(A_log))`` a head. State a head, P x N float32:
  ``S_t = a_t S_(t-1) + dt_t x_t B_t^T``; ``y_t = S_t C_t + D x_t``. Then ``y
  = y * silu(z)``, RMS-normalised in G groups of H P / G channels, times the
  norm's weight; output ``y W_out``. A sequence keeps ``S`` and the
  convolution's last K - 1 inputs, whatever its length.
* ``*``, attention: grouped-query causal softmax at ``1 / sqrt(head_dim)``,
  the head size its own key (not hidden / heads), NO rotary and no other
  position embedding. A sequence keeps keys and values a token, in pages.
* ``E``, experts: ``moe_mla.SparseExperts`` with ``relu(x W_up)^2 W_down``
  experts (no gate), the shared expert ``moe_shared_expert_intermediate_size``
  wide. A sequence keeps nothing.

The model tells the serving engine what EACH layer keeps
(:meth:`NemotronHForCausalLM.layer_keeps`; ``generation.sequence_keeps``
asks), and the engine hands every layer its own kind of cache: a
``StateCache`` over the layer's two state arrays (``ops/pallas/ssd``: a
chunk of new tokens through ``ssd_chunk``, one token a row through
``ssd_decode``, the state updated in place; positions at or past a row's true
length are masked out of the state and out of the carried inputs), a
``PagedKVCache`` (``llama.cached_attention``), or a ``LayerPass`` that only
carries which rows are live in and the layer's counts out. With no caches
the Mamba-2 layers run the same chunked form from a zero state.

Not here: the training path (``labels=``), a prefix cache or a handoff over
the state, the layers sharded over chips, experts spread over chips with
their exchange. ROADMAP M4 / D1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import Layer
from ..nn import initializer as I
from ..nn.layers_common import Embedding, LayerList, Linear
from ..nn.layers_norm import RMSNorm
from ..ops import reshape, scaled_dot_product_attention
from .generation import StateCache
from .llama import cached_attention
from .moe_mla import STEP_STAT_NAMES as MOE_STAT_NAMES
from .moe_mla import SparseExperts

__all__ = ["NemotronHConfig", "Mamba2Mixer", "NemotronHAttention",
           "NemotronHBlock", "NemotronHModel", "NemotronHForCausalLM",
           "nemotron_h_tiny_config", "STEP_STAT_NAMES"]

# what the layers count a decode step, ONE vector: a Mamba-2 layer fills the
# first two, an expert layer the last four; the engine's segment sums them
# over the layers and the steps (``serving.<name>_total``)
STEP_STAT_NAMES = ("state_rows_live", "state_layer_steps") + MOE_STAT_NAMES


class NemotronHConfig:
    """Keys as the family's ``config.json`` has them."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 num_hidden_layers=52,
                 hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM"
                                         "*EMEMEMEM*EMEMEMEME",
                 mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
                 ssm_state_size=128, conv_kernel=4, chunk_size=128,
                 use_conv_bias=True, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=0.0001, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, n_routed_experts=128,
                 num_experts_per_tok=6, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_shared_experts=1, routed_scaling_factor=2.5,
                 norm_topk_prob=True, layer_norm_epsilon=1e-5,
                 max_position_embeddings=4096, initializer_range=0.02,
                 experts_held=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.hybrid_override_pattern = hybrid_override_pattern
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.n_groups = n_groups
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.use_conv_bias = use_conv_bias
        self.time_step_min = time_step_min
        self.time_step_max = time_step_max
        self.time_step_floor = time_step_floor
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.n_shared_experts = n_shared_experts
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.layer_norm_epsilon = layer_norm_epsilon
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        # what ``SparseExperts`` reads under its own names
        self.expert_activation = "relu2"
        self.shared_intermediate_size = moe_shared_expert_intermediate_size
        # (first, count) of the routed experts this chip holds; None = all
        self.experts_held = (tuple(experts_held) if experts_held is not None
                             else (0, n_routed_experts))
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {n_routed_experts} routed experts")
        pattern = hybrid_override_pattern
        if len(pattern) != num_hidden_layers or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} has to name "
                f"{num_hidden_layers} layers, each M, E or *")
        if mamba_num_heads % n_groups or \
                num_attention_heads % num_key_value_heads:
            raise ValueError("heads have to be a multiple of their groups")

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def nemotron_h_tiny_config(**overrides):
    base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=5,
                hybrid_override_pattern="MEM*E", mamba_num_heads=4,
                mamba_head_dim=8, n_groups=2, ssm_state_size=16,
                conv_kernel=4, chunk_size=16, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, n_routed_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=32,
                moe_shared_expert_intermediate_size=48,
                max_position_embeddings=128)
    base.update(overrides)
    return NemotronHConfig(**base)


def _linear(n_in, n_out, cfg):
    return Linear(n_in, n_out, weight_attr=I.Normal(0.0, cfg.initializer_range),
                  bias_attr=False)


def _stats(**filled):
    """The step's vector with this layer's entries filled."""
    return jnp.stack([jnp.asarray(filled.get(name, 0), jnp.int32)
                      for name in STEP_STAT_NAMES])


class _DtBias(I.Initializer):
    """``dt_bias`` as the family initialises it: the inverse softplus of a
    log-uniform draw in [time_step_min, time_step_max], floored."""

    def __init__(self, cfg):
        self.lo, self.hi = (math.log(cfg.time_step_min),
                            math.log(cfg.time_step_max))
        self.floor = cfg.time_step_floor

    def generate(self, shape, dtype, key):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, self.lo, self.hi)), self.floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class _ALog(I.Initializer):
    """``A_log``: the log of a uniform draw in [1, 16]."""

    def generate(self, shape, dtype, key):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)


class Mamba2Mixer(Layer):
    """The selective state-space layer; the cache is (state, carried conv
    inputs)."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        c = config
        inner, h = c.mamba_inner, c.mamba_num_heads
        self.in_proj = _linear(c.hidden_size, inner + c.conv_dim + h, c)
        self.conv_weight = self.create_parameter(
            (c.conv_kernel, c.conv_dim),
            default_initializer=I.Normal(0.0, c.initializer_range))
        self.conv_bias = self.create_parameter(
            (c.conv_dim,), default_initializer=I.Constant(0.0))
        self.A_log = self.create_parameter(
            (h,), dtype="float32", default_initializer=_ALog())
        self.D = self.create_parameter(
            (h,), dtype="float32", default_initializer=I.Constant(1.0))
        self.dt_bias = self.create_parameter(
            (h,), dtype="float32", default_initializer=_DtBias(c))
        self.norm_weight = self.create_parameter(
            (inner,), default_initializer=I.Constant(1.0))
        self.out_proj = _linear(inner, c.hidden_size, c)

    def keeps(self):
        c = self.config
        return ("state",
                ((c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size),
                 jnp.float32),
                ((c.conv_kernel - 1, c.conv_dim), jnp.float32))

    def forward(self, u, cache=None):
        from ..core.flags import flag as _flag
        from ..ops.pallas import ssd as S

        c = self.config
        b, s, _ = u.shape
        h, p, g, n = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                      c.ssm_state_size)
        inner = c.mamba_inner
        proj = self.in_proj(u)._value
        z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + c.conv_dim],
                      proj[..., inner + c.conv_dim:])
        own = cache is None
        if own:                      # no engine: every row from a zero state
            cache = StateCache(*(jnp.zeros((b,) + shape, dt)
                                 for shape, dt in self.keeps()[1:]),
                               jnp.arange(b, dtype=jnp.int32))
        rows, live = cache.rows, cache.live
        kernels = _flag("FLAGS_use_pallas_kernels")
        with jax.named_scope("ssm_conv"):
            carried = cache.z[rows]
            xbc, keep = S.causal_conv(xbc, self.conv_weight._value,
                                      self.conv_bias._value, carried,
                                      cache.true_lens)
            if live is not None:     # a row that is not live keeps its own
                keep = jnp.where(live[:, None, None], keep, carried)
            cache.z = cache.z.at[rows].set(keep)
            xbc = jax.nn.silu(xbc).astype(proj.dtype)
        x = xbc[..., :inner].reshape(b, s, h, p)
        bm = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
        cm = xbc[..., inner + g * n:].reshape(b, s, g, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias._value)
        neg_a = -jnp.exp(self.A_log._value.astype(jnp.float32))
        with jax.named_scope("ssm"):
            if s == 1 and cache.true_lens is None and not own:
                step = S.ssd_decode if kernels else S.ssd_decode_reference
                y, cache.s = step(x[:, 0], dt[:, 0],
                                  jnp.exp(dt[:, 0] * neg_a), bm[:, 0],
                                  cm[:, 0], cache.s, rows, live)
                y = y[:, None]
                n_live = (jnp.sum(live, dtype=jnp.int32)
                          if live is not None else b)
                cache.stats = _stats(state_rows_live=n_live,
                                     state_layer_steps=1)
            else:
                y = self._chunks(x, S.mask_steps(dt, cache.true_lens), neg_a,
                                 bm, cm, cache, kernels)
        cache.length = cache.length + s
        y = y + self.D._value[:, None] * x.astype(jnp.float32)
        y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(jnp.float32))
        # gated RMS norm in G groups of inner / G channels
        yg = y.reshape(b, s, g, inner // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                                + c.layer_norm_epsilon)
        y = (yg.reshape(b, s, inner)
             * self.norm_weight._value.astype(jnp.float32)).astype(proj.dtype)
        out = self.out_proj(Tensor._from_value(y))
        return out if own else (out, cache)

    def _chunks(self, x, dt, neg_a, bm, cm, cache, kernels):
        """The new tokens a ``chunk_size`` at a time through the state."""
        from ..ops.pallas import ssd as S

        s, n = x.shape[1], self.config.chunk_size
        chunk = S.ssd_chunk if kernels else S.ssd_chunk_reference
        pad = -s % n
        if pad:                      # masked tail: dt 0
            x, bm, cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                         for a in (x, bm, cm))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        ys = []
        for c0 in range(0, s + pad, n):
            sl = slice(c0, c0 + n)
            y, cache.s = chunk(x[:, sl], dt[:, sl], dt[:, sl] * neg_a,
                               bm[:, sl], cm[:, sl], cache.s, cache.rows)
            ys.append(y)
        y = jnp.concatenate(ys, 1) if len(ys) > 1 else ys[0]
        return y[:, :s] if pad else y


class NemotronHAttention(Layer):
    """Grouped-query softmax attention without position embedding; the head
    size is the config's own."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        c = config
        h, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.q_proj = _linear(c.hidden_size, h * d, c)
        self.k_proj = _linear(c.hidden_size, kv * d, c)
        self.v_proj = _linear(c.hidden_size, kv * d, c)
        self.o_proj = _linear(h * d, c.hidden_size, c)

    def keeps(self):
        c = self.config
        shape = (c.num_key_value_heads, c.head_dim)
        return ("pages", shape, shape)

    def forward(self, u, cache=None):
        c = self.config
        b, s, _ = u.shape
        h, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = reshape(self.q_proj(u), [b, s, h, d])
        k = reshape(self.k_proj(u), [b, s, kv, d])
        v = reshape(self.v_proj(u), [b, s, kv, d])
        if cache is None:
            out = scaled_dot_product_attention(q, k, v, is_causal=True)
        else:
            out = cached_attention(q, k, v, cache, cache.length, s)
        out = self.o_proj(reshape(out, [b, s, h * d]))
        return out if cache is None else (out, cache)


class NemotronHBlock(Layer):
    """``x + Mixer(rmsnorm(x))``, the mixer by its character."""

    SCOPES = {"M": "mamba", "*": "attn", "E": "moe"}

    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = RMSNorm(config.hidden_size,
                            epsilon=config.layer_norm_epsilon)
        self.mixer = {"M": Mamba2Mixer, "*": NemotronHAttention,
                      "E": SparseExperts}[kind](config)

    def keeps(self):
        return None if self.kind == "E" else self.mixer.keeps()

    def forward(self, hidden_states, cache=None):
        u = self.norm(hidden_states)
        with jax.named_scope(self.SCOPES[self.kind]):
            if self.kind == "E":
                out, stats = self.mixer(u, live=getattr(cache, "live", None))
                if cache is not None:
                    cache.stats = _stats(**dict(zip(MOE_STAT_NAMES, stats)))
            elif cache is None:
                out = self.mixer(u)
            else:
                out, cache = self.mixer(u, cache=cache)
        hidden_states = hidden_states + out
        return hidden_states if cache is None else (hidden_states, cache)


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range))
        self.layers = LayerList([
            NemotronHBlock(config, kind)
            for kind in config.hybrid_override_pattern])
        self.norm = RMSNorm(config.hidden_size,
                            epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, caches=None):
        with jax.named_scope("embed"):
            hidden = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                hidden, cache = layer(hidden, cache=caches[i])
                new_caches.append(cache)
            else:
                hidden = layer(hidden)
        return (hidden, new_caches) if caches is not None else hidden


class NemotronHForCausalLM(Layer):
    """Causal LM over :class:`NemotronHModel`, with ``LlamaForCausalLM``'s
    call shape, so the serving engine and the frontend take it as they take
    the dense model. Where the caches say how many of the new tokens are
    real (a prefill), the head is taken at each row's true last position
    alone and the logits are (B, 1, vocab)."""

    step_stat_names = STEP_STAT_NAMES

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.model = NemotronHModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)

    def layer_keeps(self):
        """What one sequence keeps in EACH layer: a state (the shapes after
        the slot dimension, and types), pages (the trailing shapes of the
        two pools) or None."""
        return [layer.keeps() for layer in self.model.layers]

    def forward(self, input_ids, attn_mask=None, caches=None, labels=None):
        if labels is not None:
            raise NotImplementedError(
                "nemotron_h has no training path yet (the chunked state-"
                "space form's and the grouped product's backward): ROADMAP "
                "M4")
        if attn_mask is not None:
            raise NotImplementedError(
                "nemotron_h takes no attention mask: its layers are causal "
                "by construction")
        out = self.model(input_ids, caches=caches)
        hidden = out[0] if caches is not None else out
        true_lens = next((c.true_lens for c in caches or ()
                          if isinstance(c, StateCache)), None)
        with jax.named_scope("final_norm"):
            if true_lens is not None:
                idx = (true_lens - 1).astype(jnp.int32)[:, None, None]
                hidden = Tensor._from_value(jnp.take_along_axis(
                    hidden._value, jnp.broadcast_to(
                        idx, (hidden.shape[0], 1, hidden.shape[-1])), axis=1))
            hidden = self.model.norm(hidden)
        with jax.named_scope("lm_head"):
            logits = self.lm_head(hidden)
        return (logits, out[1]) if caches is not None else logits
