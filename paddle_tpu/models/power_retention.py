"""Decoder whose every attention is POWER RETENTION (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239): a Qwen3-shaped
block (per-head RMS norm on queries and keys, rotary, SwiGLU) with a gated
linear attention of power 2 in the place of softmax attention, on the serving
path.

Layer equations (``x`` the layer's input, ``h`` a query head, ``c = h //
(heads / kv heads)`` its kv head, ``d`` the head size, ``s = 1 / sqrt(d)``,
sums over ``j <= t``):

    u      = rmsnorm(x)
    q_h    = rope(rmsnorm_head(W_q u)_h)     k_c = rope(rmsnorm_head(W_k u)_c)
    v_c    = (W_v u)_c
    log g_c,t = log_sigmoid((W_g u_t + b_g)_c)        float32, a gate a kv head
    a_h(t,j)  = exp(sum_{j<l<=t} log g_c,l) * (s q_h,t . k_c,j)^2
    y_h,t  = sum_j a_h(t,j) v_c,j / sum_j a_h(t,j)
    out    = x + W_o concat_h(y_h);  then  out + SwiGLU(rmsnorm(out))

and as a recurrence over ``phi(z)``, the d(d+1)/2 monomials ``z_a z_b`` with
``phi(q) . phi(k) = (q . k)^2``:

    S_c,t = g_c,t S_c,t-1 + phi(s k_c,t) v_c,t^T     Z_c,t = g_c,t Z_c,t-1 + phi(s k_c,t)
    y_h,t = phi(q_h,t)^T S_c,t / phi(q_h,t)^T Z_c,t

A sequence of this model keeps NO page: it keeps one state ``(S, Z)`` a layer
in float32, whatever its length, and says so to the serving engine through
:meth:`PowerRetentionForCausalLM.sequence_state` (``generation.sequence_keeps``
asks). With no cache the layer runs the attention form in row blocks; with
the engine's ``generation.StateCache`` it runs the recurrence: a chunk of new tokens
through ``ops/pallas/retention.power_retention_chunk`` (quadratic inside the
chunk, the slot's state read and carried on), one token a row through
``power_retention_decode`` (a work list of the live rows, the state updated
in place). Positions at or past a row's true length are masked out of the
update (gate 1, addend 0): a recurrence that swallowed a padding token would
be wrong for ever after. The norm, rotary and SwiGLU are ``models/llama.py``'s.

Not here: the training path (``labels=``; the chunked form's backward), a
prefix cache or a handoff over the state (a snapshot a boundary), the state
sharded over chips. ROADMAP M4 / D1.
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
from ..ops import reshape, rotary_position_embedding
from .llama import LlamaMLP, _per_seq_lengths, _rope_tables

__all__ = ["PowerRetentionConfig", "PowerRetention",
           "PowerRetentionDecoderLayer", "PowerRetentionModel",
           "PowerRetentionForCausalLM", "power_retention_tiny_config",
           "STEP_STAT_NAMES", "GATE_BIAS_RANGE"]

# what a retention layer counts a decode step, summed over the layers by
# the engine's segment (``serving.<name>_total``)
STEP_STAT_NAMES = ("state_rows_live", "state_layer_steps")
# the engine's prompt chunk; longer runs of new tokens go a chunk at a time
CHUNK = 128
# the gate's bias is drawn uniform in this range, so that g lies in (0.982,
# 0.9997): with a zero-mean gate the state would forget in twenty tokens
GATE_BIAS_RANGE = (4.0, 8.0)
_ROW_BLOCK = 512


class PowerRetentionConfig:
    """Keys as the family's ``config.json`` has them."""

    def __init__(self, vocab_size=151936, hidden_size=5120,
                 intermediate_size=17408, num_hidden_layers=40,
                 num_attention_heads=40, num_key_value_heads=8, head_dim=128,
                 max_position_embeddings=32768, rms_norm_eps=1e-6,
                 rope_theta=1000000.0, initializer_range=0.02):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        if num_attention_heads % num_key_value_heads or head_dim % 2:
            raise ValueError("query heads must be a multiple of the kv heads "
                             "and the head size even")


def power_retention_tiny_config(**overrides):
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16,
                max_position_embeddings=128)
    base.update(overrides)
    return PowerRetentionConfig(**base)


def _linear(n_in, n_out, cfg):
    return Linear(n_in, n_out, weight_attr=I.Normal(0.0, cfg.initializer_range),
                  bias_attr=False)


def retention_attention_form(q, k, v, logg):
    """The attention form, rows in blocks: ``q`` (B, S, H, d), scaled ``k``
    and ``v`` (B, S, KV, d), ``logg`` (B, S, KV) float32 -> (B, S, H, d)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    cum = jnp.cumsum(logg, axis=1).transpose(0, 2, 1)          # (B, KV, S)
    qg = q.reshape(b, s, kv, h // kv, d)
    cols = jnp.arange(s)
    out = []
    for r0 in range(0, s, _ROW_BLOCK):
        r1 = min(r0 + _ROW_BLOCK, s)
        sc = jnp.einsum("btcgd,bjcd->bcgtj", qg[:, r0:r1], k,
                        preferred_element_type=jnp.float32)
        see = cols[None, :] <= jnp.arange(r0, r1)[:, None]
        gap = cum[:, :, r0:r1, None] - cum[:, :, None, :]
        a = jnp.where(see, jnp.exp(jnp.where(see, gap, 0.0))[:, :, None]
                      * sc * sc, 0.0)
        num = jnp.einsum("bcgtj,bjcd->btcgd", a.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        den = jnp.sum(a, -1).transpose(0, 3, 1, 2)[..., None]
        out.append((num / den).astype(q.dtype))
    return jnp.concatenate(out, 1).reshape(b, s, h, d)


class PowerRetention(Layer):
    """Power retention in the place of attention; the cache is a state."""

    def __init__(self, config: PowerRetentionConfig):
        super().__init__()
        self.config = config
        c = config
        h, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        self.q_proj = _linear(c.hidden_size, h * d, c)
        self.k_proj = _linear(c.hidden_size, kv * d, c)
        self.v_proj = _linear(c.hidden_size, kv * d, c)
        self.o_proj = _linear(h * d, c.hidden_size, c)
        self.q_norm = RMSNorm(d, epsilon=c.rms_norm_eps)
        self.k_norm = RMSNorm(d, epsilon=c.rms_norm_eps)
        # one gate a kv head: log_sigmoid of a linear map of the normed
        # input; the bias keeps g near 1 so that the state remembers
        self.g_proj = _linear(c.hidden_size, kv, c)
        self.g_bias = self.create_parameter(
            (kv,), dtype="float32",
            default_initializer=I.Uniform(*GATE_BIAS_RANGE))
        cos, sin = _rope_tables(d, c.max_position_embeddings, c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        self.scale = 1.0 / math.sqrt(d)

    def forward(self, hidden_states, attn_mask=None, cache=None):
        c = self.config
        b, s, _ = hidden_states.shape
        h, kv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        q = self.q_norm(reshape(self.q_proj(hidden_states), [b, s, h, d]))
        k = self.k_norm(reshape(self.k_proj(hidden_states), [b, s, kv, d]))
        v = reshape(self.v_proj(hidden_states), [b, s, kv, d])._value
        with jax.named_scope("retention_gate"):
            logg = jax.nn.log_sigmoid(
                jnp.dot(hidden_states._value.astype(jnp.float32),
                        self.g_proj.weight._value.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
                + self.g_bias._value)                          # (B, S, KV)
        offset = cache.length if cache is not None else 0
        position_ids = None
        if _per_seq_lengths(offset):
            position_ids = Tensor._from_value(
                jnp.arange(s)[None, :] + offset[:, None])
        elif not isinstance(offset, int) or offset > 0:
            position_ids = Tensor._from_value(jnp.arange(s) + offset)
        q, k = rotary_position_embedding(q, k, self.rope_cos, self.rope_sin,
                                         position_ids=position_ids)
        q = q._value
        k = (k._value * self.scale).astype(q.dtype)
        if cache is None:
            if attn_mask is not None:
                raise NotImplementedError(
                    "power retention takes no attention mask: the causal "
                    "gated sum is the layer")
            y = retention_attention_form(q, k, v, logg)
        else:
            y = self._recurrent(q, k, v, logg, cache)
        out = self.o_proj(Tensor._from_value(y.reshape(b, s, h * d)))
        return (out, cache) if cache is not None else out

    def _recurrent(self, q, k, v, logg, cache):
        """The new tokens through the slot's state; the cache leaves with
        the state they made and what the step counted."""
        from ..core.flags import flag as _flag
        from ..ops.pallas import retention as R

        b, s = q.shape[0], q.shape[1]
        kernels = _flag("FLAGS_use_pallas_kernels")
        rows = cache.rows
        if s == 1 and cache.true_lens is None:
            step = (R.power_retention_decode if kernels
                    else R.retention_decode_reference)
            y, cache.s, cache.z = step(q[:, 0], k[:, 0], v[:, 0], logg[:, 0],
                                       cache.s, cache.z, rows, cache.live)
            live = (jnp.sum(cache.live, dtype=jnp.int32)
                    if cache.live is not None else jnp.int32(b))
            cache.stats = jnp.stack([live, jnp.int32(1)])
            y = y[:, None]
        else:
            k, logg = R.mask_chunk(k, logg, cache.true_lens)
            chunk = (R.power_retention_chunk if kernels
                     else R.retention_chunk_reference)
            pad = -s % CHUNK if s > CHUNK else 0
            if pad:                  # masked tail: gate 1, addend 0
                q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                           for a in (q, k, v))
                logg = jnp.pad(logg, ((0, 0), (0, pad), (0, 0)))
            ys = []
            for c0 in range(0, s + pad, CHUNK):
                sl = slice(c0, c0 + CHUNK)
                y, cache.s, cache.z = chunk(q[:, sl], k[:, sl], v[:, sl],
                                            logg[:, sl], cache.s, cache.z,
                                            rows)
                ys.append(y)
            y = jnp.concatenate(ys, 1)[:, :s] if len(ys) > 1 else ys[0]
        cache.length = cache.length + s
        return y


class PowerRetentionDecoderLayer(Layer):
    def __init__(self, config: PowerRetentionConfig):
        super().__init__()
        self.self_attn = PowerRetention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, hidden_states, attn_mask=None, cache=None):
        residual = hidden_states
        with jax.named_scope("retention"):
            out = self.self_attn(self.input_layernorm(hidden_states),
                                 attn_mask=attn_mask, cache=cache)
            if cache is not None:
                out, cache = out
            hidden_states = residual + out
        with jax.named_scope("mlp"):
            hidden_states = hidden_states + self.mlp(
                self.post_attention_layernorm(hidden_states))
        return (hidden_states, cache) if cache is not None else hidden_states


class PowerRetentionModel(Layer):
    def __init__(self, config: PowerRetentionConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range))
        self.layers = LayerList([PowerRetentionDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, caches=None):
        with jax.named_scope("embed"):
            hidden = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                hidden, cache = layer(hidden, attn_mask=attn_mask,
                                      cache=caches[i])
                new_caches.append(cache)
            else:
                hidden = layer(hidden, attn_mask=attn_mask)
        return (hidden, new_caches) if caches is not None else hidden


class PowerRetentionForCausalLM(Layer):
    """Causal LM over :class:`PowerRetentionModel`, with
    ``LlamaForCausalLM``'s call shape, so the serving engine and the
    frontend take it as they take the dense model. Where the caches say how
    many of the new tokens are real (a prefill), the head is taken at each
    row's true last position alone and the logits are (B, 1, vocab): a
    (32, 128, 151936) float32 array would be 2.5 GB."""

    step_stat_names = STEP_STAT_NAMES

    def __init__(self, config: PowerRetentionConfig):
        super().__init__()
        self.config = config
        self.model = PowerRetentionModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)

    def sequence_state(self):
        """What one sequence keeps a layer, whatever its length: the shapes
        (after the slot dimension) and types of ``S`` and ``Z``."""
        from ..ops.pallas.retention import state_shapes

        s_shape, z_shape = state_shapes(self.config.num_key_value_heads,
                                        self.config.head_dim)
        return (s_shape, jnp.float32), (z_shape, jnp.float32)

    def forward(self, input_ids, attn_mask=None, caches=None, labels=None):
        if labels is not None:
            raise NotImplementedError(
                "power retention has no training path yet (the chunked "
                "form's backward): ROADMAP M4")
        out = self.model(input_ids, attn_mask=attn_mask, caches=caches)
        hidden = out[0] if caches is not None else out
        true_lens = caches[0].true_lens if caches else None
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
