"""Sparse-expert decoder with latent attention (the DeepSeek-V3 family's
block: multi-head latent attention + sigmoid-routed experts with a shared
expert), on the serving path.

Layer equations (x is (tokens, hidden); pre-norm residual block
``x += Attn(norm(x)); x += FFN(norm(x))``; final norm; untied head):

* Attention. ``c_q = norm(x W_qa)``; ``q = c_q W_qb``, heads of
  ``[q_nope | q_rope]``. ``[c_kv | k_rope] = x W_kva``; ``c_kv =
  norm(c_kv)``; RoPE over interleaved pairs (2i, 2i+1) on ``q_rope`` and on
  the ONE ``k_rope`` all heads share. Plain form: ``[k_nope | v]_h = c_kv
  W_kvb,h``; scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope +
  rope)``, causal softmax, ``o_h = sum p v_h``, output ``concat(o_h) W_o``.
  Absorbed form, the same numbers: ``q_lat,h = q_nope,h W_kvb,h^K^T``,
  scores ``q_lat . c_kv + q_rope . k_rope``, ``o_lat = sum p c_kv``, ``o_h =
  o_lat W_kvb,h^V``. The cache holds, a token a layer, the normed ``c_kv``
  and the rotated ``k_rope``: the model tells the engine so through
  :meth:`MoEMLAForCausalLM.kv_page_shapes`.
* Sparse layer. Router in float32: ``s = sigmoid(x W_g)``; the
  ``num_experts_per_tok`` largest of ``s + b`` are chosen (the correction
  bias chooses and does not weigh), weights ``s_k / sum s`` times
  ``routed_scaling_factor``; ``y = sum_k w_k E_k(x) + E_shared(x)``,
  ``E(x) = (silu(x W_gate) * x W_up) W_down``. No token is dropped.
* The first ``first_k_dense_replace`` layers: the same attention and a
  dense SwiGLU.

A fresh prefill (static base 0) runs the plain form through the shared
sdpa / flash path (v padded to the key's head size); a decode step runs
the absorbed form through ``ops/pallas/mla_attention.paged_mla_attention``;
chunked and resume prefill attend to the latent cache in the absorbed form
as a masked composition. The expert layer is told which experts it holds
(``experts_held = (first, count)``): it routes over all of them, computes
the assignments that fall on its range through ``ops/pallas/moe_gmm`` (rows
sorted by expert, an expert without rows is never read) and adds the shared
expert; nothing stands in for absent chips. RoPE's pair layout is folded
into a permutation (evens | odds) of the rope dimensions of q and k alike,
which leaves every score as it was and lets the shared neox-style
``rotary_position_embedding`` do the rotation.

Not here: the training path (``labels=``; a grouped-matmul backward), the
multi-token-prediction module, experts spread over chips. ROADMAP M1/M3.
"""
from __future__ import annotations

import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..nn import Layer
from ..nn import initializer as I
from ..nn.layers_common import Embedding, LayerList, Linear
from ..nn.layers_norm import RMSNorm
from ..ops import (
    reshape,
    rotary_position_embedding,
    scaled_dot_product_attention,
)
from .llama import (
    LlamaMLP,
    PagedKVCache,
    StaticCache,
    _per_seq_lengths,
    _rope_tables,
)

__all__ = ["MoEMLAConfig", "LatentAttention", "SparseExperts", "UngatedMLP",
           "MoEMLADecoderLayer", "MoEMLAModel", "MoEMLAForCausalLM",
           "moe_mla_tiny_config", "route", "STEP_STAT_NAMES"]

# what a sparse layer counts a decode step, summed over the layers by the
# model and over a segment's steps by the engine (``serving.<name>_total``)
STEP_STAT_NAMES = ("moe_assignments", "moe_experts_hit", "moe_load_max",
                   "moe_layer_steps")


class MoEMLAConfig:
    """Keys as the family's ``config.json`` has them."""

    def __init__(self, vocab_size=129280, hidden_size=2048,
                 intermediate_size=7168, moe_intermediate_size=768,
                 num_hidden_layers=40, num_attention_heads=32,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=256,
                 num_experts_per_tok=8, n_shared_experts=1,
                 first_k_dense_replace=1, routed_scaling_factor=2.5,
                 norm_topk_prob=True, max_position_embeddings=4096,
                 rms_norm_eps=1e-6, rope_theta=32000000.0,
                 initializer_range=0.02, experts_held=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_shared_experts = n_shared_experts
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        # (first, count) of the routed experts this chip holds; None = all
        self.experts_held = (tuple(experts_held) if experts_held is not None
                             else (0, n_routed_experts))
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {n_routed_experts} routed experts")

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def moe_mla_tiny_config(**overrides):
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=16, num_experts_per_tok=4,
                max_position_embeddings=128)
    base.update(overrides)
    return MoEMLAConfig(**base)


def _linear(n_in, n_out, cfg):
    return Linear(n_in, n_out, weight_attr=I.Normal(0.0, cfg.initializer_range),
                  bias_attr=False)


class LatentAttention(Layer):
    """Multi-head latent attention; the cache is (latent, rope key)."""

    def __init__(self, config: MoEMLAConfig):
        super().__init__()
        self.config = config
        c = config
        h = c.num_attention_heads
        self.q_a_proj = _linear(c.hidden_size, c.q_lora_rank, c)
        self.q_a_layernorm = RMSNorm(c.q_lora_rank, epsilon=c.rms_norm_eps)
        self.q_b_proj = _linear(c.q_lora_rank, h * c.qk_head_dim, c)
        self.kv_a_proj_with_mqa = _linear(
            c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim, c)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, epsilon=c.rms_norm_eps)
        self.kv_b_proj = _linear(
            c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim), c)
        self.o_proj = _linear(h * c.v_head_dim, c.hidden_size, c)
        cos, sin = _rope_tables(c.qk_rope_head_dim, c.max_position_embeddings,
                                c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        r = c.qk_rope_head_dim
        # pairs (2i, 2i+1) -> (i, i + r/2): the neox layout of the same
        # rotation; applied to q and k alike, so no score changes
        self._pairs = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])
        self.scale = 1.0 / math.sqrt(c.qk_head_dim)

    def _kvb(self):
        """kv_b_proj as (latent, heads, nope | v): its key and value
        halves, for the absorbed form."""
        c = self.config
        w = self.kv_b_proj.weight._value.reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def forward(self, hidden_states, attn_mask=None, cache=None):
        c = self.config
        b, s, _ = hidden_states.shape
        h, nope, rope = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim
        q = reshape(self.q_b_proj(self.q_a_layernorm(
            self.q_a_proj(hidden_states))), [b, s, h, c.qk_head_dim])
        kv_a = self.kv_a_proj_with_mqa(hidden_states)
        c_kv = self.kv_a_layernorm(kv_a[:, :, :c.kv_lora_rank])
        q_nope = q[:, :, :, :nope]
        q_rope = Tensor._from_value(q._value[..., nope:][..., self._pairs])
        k_rope = Tensor._from_value(
            kv_a._value[:, :, None, c.kv_lora_rank:][..., self._pairs])
        offset = cache.length if cache is not None else 0
        position_ids = None
        if _per_seq_lengths(offset):
            position_ids = Tensor._from_value(
                jnp.arange(s)[None, :] + offset[:, None])
        elif not isinstance(offset, int) or offset > 0:
            position_ids = Tensor._from_value(jnp.arange(s) + offset)
        q_rope, k_rope = rotary_position_embedding(
            q_rope, k_rope, self.rope_cos, self.rope_sin,
            position_ids=position_ids)
        if cache is not None:
            cache.update(c_kv._value, k_rope._value[:, :, 0, :])
        fresh = cache is None or (isinstance(offset, int) and offset == 0)
        if fresh:
            out = self._plain(q_nope, q_rope, c_kv, k_rope, attn_mask)
        else:
            out = self._absorbed(q_nope._value, q_rope._value, cache, offset)
        out = self.o_proj(reshape(out, [b, s, h * c.v_head_dim]))
        return (out, cache) if cache is not None else out

    def _plain(self, q_nope, q_rope, c_kv, k_rope, attn_mask):
        """The new tokens among themselves, per-head keys and values made
        from the latent: the shared sdpa (flash where it qualifies). v is
        padded to the key's head size, which the kernel wants equal."""
        c = self.config
        b, s = c_kv.shape[0], c_kv.shape[1]
        h, nope = c.num_attention_heads, c.qk_nope_head_dim
        kv = reshape(self.kv_b_proj(c_kv), [b, s, h, nope + c.v_head_dim])
        k = jnp.concatenate(
            [kv._value[..., :nope],
             jnp.broadcast_to(k_rope._value, (b, s, h, k_rope.shape[-1]))],
            axis=-1)
        q = jnp.concatenate([q_nope._value, q_rope._value], axis=-1)
        v = jnp.pad(kv._value[..., nope:],
                    ((0, 0),) * 3 + ((0, c.qk_head_dim - c.v_head_dim),))
        out = scaled_dot_product_attention(
            Tensor._from_value(q), Tensor._from_value(k),
            Tensor._from_value(v), attn_mask=attn_mask,
            is_causal=attn_mask is None)
        return Tensor._from_value(out._value[..., :c.v_head_dim])

    def _absorbed(self, q_nope, q_rope, cache, offset):
        """Queries carried into the latent space, against the cache."""
        from ..core.flags import flag as _flag
        from ..ops.pallas.mla_attention import (
            latent_attend, paged_mla_attention)

        b, s = q_nope.shape[0], q_nope.shape[1]
        w_k, w_v = self._kvb()
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k)
        clen = cache.length              # post-update: the new tokens too
        paged = isinstance(cache, PagedKVCache)
        if paged:
            ap = getattr(cache, "attn_pages", None)
            if s == 1 and _flag("FLAGS_use_pallas_kernels"):
                lengths = (clen.astype(jnp.int32) if _per_seq_lengths(clen)
                           else jnp.full((b,), clen, jnp.int32))
                o_lat = paged_mla_attention(
                    q_lat[:, 0], q_rope[:, 0], cache.k_pages, cache.v_pages,
                    cache.tables, lengths, self.scale, pages_per_seq=ap)[:, None]
                return Tensor._from_value(
                    jnp.einsum("bshc,chd->bshd", o_lat, w_v))
            tables = cache.tables
            if ap is not None and ap < tables.shape[1]:
                tables = tables[:, :ap]
            c_all = cache.k_pages[tables].reshape(
                b, -1, cache.k_pages.shape[-1])
            r_all = cache.v_pages[tables].reshape(
                b, -1, cache.v_pages.shape[-1])
        else:
            c_all, r_all = cache.k, cache.v
        cols = jnp.arange(c_all.shape[1])
        if _per_seq_lengths(offset):
            rows = jnp.arange(s)[None, :] + offset[:, None]       # (B, s)
            mask = cols[None, None, None, :] <= rows[:, None, :, None]
        else:
            rows = jnp.arange(s)[:, None] + offset
            mask = (cols[None, :] <= rows)[None, None, :, :]
        o_lat = latent_attend(q_lat, q_rope, c_all, r_all, mask, self.scale)
        return Tensor._from_value(jnp.einsum("bshc,chd->bshd", o_lat, w_v))


def route(x, gate_w, bias, top_k, scaling, norm_topk_prob=True):
    """The router, in float32: ``(ids, weights)``, each (tokens, top_k).
    ``s = sigmoid(x W_g)``; the ``top_k`` largest of ``s + bias`` are
    chosen, and weigh by ``s`` alone."""
    logits = jnp.dot(x.astype(jnp.float32), gate_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), w * scaling


class UngatedMLP(Layer):
    """``act(x W_up) W_down`` with ``act = relu(.)^2``: the expert of a
    family that does not gate (``LlamaMLP`` is the gated one)."""

    def __init__(self, config):
        super().__init__()
        self.up_proj = _linear(config.hidden_size, config.intermediate_size,
                               config)
        self.down_proj = _linear(config.intermediate_size,
                                 config.hidden_size, config)

    def forward(self, x):
        h = self.up_proj(x)._value
        return self.down_proj(Tensor._from_value(
            jnp.square(jax.nn.relu(h)).astype(h.dtype)))


class SparseExperts(Layer):
    """Router over ALL routed experts, the experts this chip holds, and
    the shared expert. ``forward(x, live)`` returns the layer's output and
    its step statistics (int32, ``STEP_STAT_NAMES``).

    The expert is the model's: ``config.expert_activation`` ``"swiglu"``
    (the default: ``(silu(x W_gate) * x W_up) W_down``, the held experts
    stacked as ``experts_gate_up`` = [gate | up]) or ``"relu2"``
    (``relu(x W_up)^2 W_down``, stacked as ``experts_up``); the shared
    expert is ``config.shared_intermediate_size`` wide (default: the routed
    width times ``n_shared_experts``). ``experts_up`` has its columns padded
    to a multiple of 128 (1,856 -> 1,920; the grouped product reads and
    multiplies the padding with the rest, 3.4 % more bytes an expert, and
    its 64 columns of the result are sliced away before the activation): the
    chip lays a matrix out in 128-lane tiles whatever its width, and a
    stack whose width is no multiple is transposed whole, every call, on
    its way to the kernel."""

    def __init__(self, config):
        super().__init__()
        self.config = config
        c = config
        init = I.Normal(0.0, c.initializer_range)
        first, count = c.experts_held
        self.gate = _linear(c.hidden_size, c.n_routed_experts, c)
        self.e_score_correction_bias = self.create_parameter(
            (c.n_routed_experts,), dtype="float32",
            default_initializer=I.Constant(0.0))
        f = c.moe_intermediate_size
        self.gated = getattr(c, "expert_activation", "swiglu") == "swiglu"
        self._stack_in = "experts_gate_up" if self.gated else "experts_up"
        setattr(self, self._stack_in, self.create_parameter(
            (count, c.hidden_size, 2 * f if self.gated
             else -(-f // 128) * 128), default_initializer=init))
        self.experts_down = self.create_parameter(
            (count, f, c.hidden_size), default_initializer=init)
        shared = SimpleNamespace(
            hidden_size=c.hidden_size,
            intermediate_size=getattr(c, "shared_intermediate_size", None)
            or f * c.n_shared_experts,
            initializer_range=c.initializer_range)
        self.shared_experts = (LlamaMLP if self.gated
                               else UngatedMLP)(shared)

    def forward(self, x, live=None):
        c = self.config
        b, s, hid = x.shape
        xv = x._value.reshape(b * s, hid)
        with jax.named_scope("moe_route"):
            ids, w = route(xv, self.gate.weight._value,
                           self.e_score_correction_bias._value,
                           c.num_experts_per_tok, c.routed_scaling_factor,
                           c.norm_topk_prob)
        rows_live = None
        if live is not None:             # (B,) rows that hold a sequence
            rows_live = jnp.repeat(live, s)
        with jax.named_scope("moe_experts"):
            routed, stats = self._routed(xv, ids, w, rows_live)
        with jax.named_scope("moe_shared"):
            shared = self.shared_experts(x)
        return Tensor._from_value(routed.reshape(b, s, hid)) + shared, stats

    def _routed(self, xv, ids, w, rows_live):
        """This chip's part of ``sum_k w_k E_k(x)``: the assignments that
        fall on the held range, sorted by expert, through two grouped
        products. Rows of dead slots are assigned to nobody."""
        from ..core.flags import flag as _flag
        from ..ops.pallas.moe_gmm import gmm_reference, moe_gmm, row_tile

        c = self.config
        first, count = c.experts_held
        t, k = ids.shape
        f = c.moe_intermediate_size
        local = ids - first
        mine = (local >= 0) & (local < count)
        if rows_live is not None:
            mine = mine & rows_live[:, None]
        flat = jnp.where(mine, local, count).reshape(-1)      # count = nobody
        sizes = jnp.bincount(flat, length=count + 1)[:count].astype(jnp.int32)
        order = jnp.argsort(flat, stable=True)                # (t * k,)
        m = t * k
        tm = row_tile(m)
        pad = -m % tm
        lhs = xv[order // k]
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        gmm = moe_gmm if _flag("FLAGS_use_pallas_kernels") else gmm_reference
        hcat = gmm(lhs, getattr(self, self._stack_in)._value, sizes)
        if self.gated:
            act = (jax.nn.silu(hcat[:, :f].astype(jnp.float32))
                   * hcat[:, f:].astype(jnp.float32)).astype(xv.dtype)
        else:
            act = jnp.square(jax.nn.relu(
                hcat[:, :f].astype(jnp.float32))).astype(xv.dtype)
        y = gmm(act, self.experts_down._value, sizes)[:m]
        # back to (token, choice) order; an assignment that is nobody's
        # here (another chip's expert, a dead slot) adds nothing: its row
        # of y is undefined, so it is selected away, not multiplied
        back = jnp.zeros((m,), jnp.int32).at[order].set(
            jnp.arange(m, dtype=jnp.int32))
        y = y[back].reshape(t, k, -1).astype(jnp.float32)
        routed = jnp.sum(jnp.where(mine[:, :, None], y * w[:, :, None], 0.0),
                         axis=1).astype(xv.dtype)
        total = jnp.sum(sizes)
        stats = jnp.stack([total, jnp.sum(sizes > 0), jnp.max(sizes),
                           (total > 0).astype(jnp.int32)]).astype(jnp.int32)
        return routed, stats


class MoEMLADecoderLayer(Layer):
    def __init__(self, config: MoEMLAConfig, layer_idx: int):
        super().__init__()
        self.self_attn = LatentAttention(config)
        self.sparse = layer_idx >= config.first_k_dense_replace
        self.mlp = SparseExperts(config) if self.sparse else LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)

    def forward(self, hidden_states, attn_mask=None, cache=None):
        residual = hidden_states
        with jax.named_scope("mla_attn"):
            attn_out = self.self_attn(self.input_layernorm(hidden_states),
                                      attn_mask=attn_mask, cache=cache)
            if cache is not None:
                attn_out, cache = attn_out
            hidden_states = residual + attn_out
        y = self.post_attention_layernorm(hidden_states)
        if self.sparse:
            y, stats = self.mlp(y, live=getattr(cache, "live", None))
            if isinstance(cache, PagedKVCache):
                cache.stats = stats      # the engine's segment carries it out
        else:
            with jax.named_scope("mlp"):
                y = self.mlp(y)
        hidden_states = hidden_states + y
        return (hidden_states, cache) if cache is not None else hidden_states


class MoEMLAModel(Layer):
    def __init__(self, config: MoEMLAConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, config.initializer_range))
        self.layers = LayerList([MoEMLADecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
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
        with jax.named_scope("final_norm"):
            hidden = self.norm(hidden)
        return (hidden, new_caches) if caches is not None else hidden


class MoEMLAForCausalLM(Layer):
    """Causal LM over :class:`MoEMLAModel`, with ``LlamaForCausalLM``'s
    call shape: ``forward(input_ids, caches=None)`` -> logits (and
    caches), so the serving engine, the frontend and ``generate()`` take
    it as they take the dense model."""

    step_stat_names = STEP_STAT_NAMES

    def __init__(self, config: MoEMLAConfig):
        super().__init__()
        self.config = config
        self.model = MoEMLAModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)

    def kv_page_shapes(self):
        """What one token keeps a layer, as the trailing shapes of the
        two page pools: the latent and the rope key. Widths lie on lanes;
        there are no kv heads to shard."""
        return ((self.config.kv_lora_rank,), (self.config.qk_rope_head_dim,))

    def forward(self, input_ids, attn_mask=None, caches=None, labels=None):
        if labels is not None:
            raise NotImplementedError(
                "sparse experts have no training path yet (no grouped-"
                "matmul backward): ROADMAP M1")
        out = self.model(input_ids, attn_mask=attn_mask, caches=caches)
        hidden = out[0] if caches is not None else out
        with jax.named_scope("lm_head"):
            logits = self.lm_head(hidden)
        return (logits, out[1]) if caches is not None else logits
