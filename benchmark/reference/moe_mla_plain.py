"""Plain reference for the sparse-expert decoder with latent attention (the
DeepSeek-V3 family's block, as JoyAI-LLM-Flash publishes it).

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: the PLAIN attention form only (per-head keys and values made from
the latent; no absorbed form, no cache), a loop over the experts (every
expert multiplies every token and a token keeps the ones it chose), no
kernels, no batching. It imports nothing of the program; weights come to it
by name (``paddle_tpu.models.moe_mla``'s parameter names) from a mapping or
from a function of the name, so a caller can make one layer's leaves at a
time: a sparse layer at the published widths is 4.96 GB in float32.

Equations (x is (S, hidden); RMSNorm eps from the configuration; pre-norm
residual block; final norm; untied head):

* attention: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` as heads of ``[nope |
  rope]``; ``[c_kv | k_rope] = x W_kva``; ``c_kv = norm(c_kv)``; RoPE on
  interleaved pairs (2i, 2i+1) (``rope_interleave``) of ``q_rope`` and of the
  one ``k_rope`` all heads share; ``[k_nope | v]_h = c_kv W_kvb,h``; scores
  ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``, causal
  softmax, ``o_h = sum p v_h``; output ``concat(o_h) W_o``.
* sparse layer (from ``first_k_dense_replace`` on): ``s = sigmoid(x W_g)``;
  the ``num_experts_per_tok`` largest of ``s + b`` are chosen; weights ``s_k
  / sum_chosen s`` times ``routed_scaling_factor``; ``y = sum_k w_k E_k(x) +
  E_shared(x)``, ``E(x) = (silu(x W_gate) * x W_up) W_down``. No token is
  dropped. ``n_group`` 1 / ``topk_group`` 1 make the group limit a no-op.
* the leading layers: the same attention and a dense SwiGLU.

Departures from the published description: the multi-token-prediction module
(``num_nextn_predict_layers`` 1) is absent, as the family allows at
inference; nothing else.

``mm`` is the matmul of every linear projection, the router's among them:
``f32`` for the reference proper, ``fp8`` for the control (the nearest
precision below the bfloat16 the configuration states).
``experts_held = (first, count)`` gives the reference the same share of the
routed experts as a chip holds; ``drop_expert`` leaves one expert's output
out (the planted fault of the rehearsal).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm_f32(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def _to_fp8(x):
    """Per-tensor scaled float8_e4m3fn, given back in float32."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm_fp8(a, b):
    return jnp.matmul(_to_fp8(a), _to_fp8(b), precision=HI)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope_interleaved(x, theta):
    """RoPE on ``x`` (S, heads, d) over the pairs (2i, 2i+1), position =
    row index."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)          # (S, d/2)
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def attention(x, lw, m, mm):
    """Latent attention, plain form, on ``x`` (S, hidden)."""
    s = x.shape[0]
    h, nope, rope, vd = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    rank, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    c_q = rms_norm(mm(x, lw["self_attn.q_a_proj.weight"]),
                   lw["self_attn.q_a_layernorm.weight"], eps)
    q = mm(c_q, lw["self_attn.q_b_proj.weight"]).reshape(s, h, nope + rope)
    kv_a = mm(x, lw["self_attn.kv_a_proj_with_mqa.weight"])
    c_kv = rms_norm(kv_a[:, :rank], lw["self_attn.kv_a_layernorm.weight"],
                    eps)
    theta = float(m["rope_theta"])
    q_rope = rope_interleaved(q[..., nope:], theta)
    k_rope = rope_interleaved(kv_a[:, None, rank:], theta)       # (S, 1, r)
    kv = mm(c_kv, lw["self_attn.kv_b_proj.weight"]).reshape(s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope, precision=HI)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope[:, 0], precision=HI)
              ) / np.sqrt(nope + rope)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v, precision=HI)
    return mm(att.reshape(s, h * vd), lw["self_attn.o_proj.weight"])


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def router(x, lw, m, mm):
    """(chosen ids, weights), each (S, top_k): choose by ``s + b``, weigh
    by ``s``."""
    s = jax.nn.sigmoid(mm(x, lw["mlp.gate.weight"]))
    _, ids = jax.lax.top_k(
        s + lw["mlp.e_score_correction_bias"].astype(F32),
        m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if m.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * m["routed_scaling_factor"]


def routed_experts(x, lw, m, mm, experts_held=None, drop_expert=None):
    """``sum_k w_k E_k(x)`` over the experts of ``experts_held`` (all by
    default): a loop over those experts, each multiplying every token."""
    first, count = experts_held or (0, m["n_routed_experts"])
    f = m["moe_intermediate_size"]
    ids, w = router(x, lw, m, mm)
    # (S, E) weight of each expert for each token, nought where not chosen
    dense_w = jnp.zeros((x.shape[0], m["n_routed_experts"]), F32).at[
        jnp.arange(x.shape[0])[:, None], ids].add(w)
    if drop_expert is not None:      # a traced index; -1 drops nobody
        dense_w = jnp.where(
            jnp.arange(m["n_routed_experts"])[None, :] == drop_expert, 0.0,
            dense_w)
    gate_up, down = lw["mlp.experts_gate_up"], lw["mlp.experts_down"]

    def one(acc, e):
        y = swiglu(x, gate_up[e][:, :f], gate_up[e][:, f:], down[e], mm)
        return acc + dense_w[:, first + e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32), jnp.arange(count))
    return out


def layer_forward(x, lw, m, mm, sparse, experts_held=None, drop_expert=None):
    """One decoder layer on ``x`` (S, hidden), float32 in and out."""
    eps = m["rms_norm_eps"]
    x = x + attention(rms_norm(x, lw["input_layernorm.weight"], eps), lw, m,
                      mm)
    h = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    if not sparse:
        return x + swiglu(h, lw["mlp.gate_proj.weight"],
                          lw["mlp.up_proj.weight"],
                          lw["mlp.down_proj.weight"], mm)
    shared = swiglu(h, lw["mlp.shared_experts.gate_proj.weight"],
                    lw["mlp.shared_experts.up_proj.weight"],
                    lw["mlp.shared_experts.down_proj.weight"], mm)
    return x + routed_experts(h, lw, m, mm, experts_held, drop_expert) \
        + shared


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool))))


@partial(jax.jit, static_argnames=("m", "mm", "sparse", "experts_held"))
def _layer_jit(x, lw, drop_expert, *, m, mm, sparse, experts_held):
    return layer_forward(x, lw, dict(m), MATMULS[mm], sparse, experts_held,
                         drop_expert)


@partial(jax.jit, static_argnames=("eps", "mm"))
def _head_jit(x_rows, norm_w, head_w, *, eps, mm):
    return MATMULS[mm](rms_norm(x_rows, norm_w, eps), head_w)


def layer_leaf_names(m: dict, i: int) -> list:
    """The leaves of layer ``i`` under ``model.layers.<i>.``."""
    attn = ["self_attn.q_a_proj.weight", "self_attn.q_a_layernorm.weight",
            "self_attn.q_b_proj.weight", "self_attn.kv_a_proj_with_mqa.weight",
            "self_attn.kv_a_layernorm.weight", "self_attn.kv_b_proj.weight",
            "self_attn.o_proj.weight"]
    if i < m["first_k_dense_replace"]:
        mlp = ["mlp.gate_proj.weight", "mlp.up_proj.weight",
               "mlp.down_proj.weight"]
    else:
        mlp = ["mlp.e_score_correction_bias", "mlp.experts_gate_up",
               "mlp.experts_down", "mlp.gate.weight",
               "mlp.shared_experts.gate_proj.weight",
               "mlp.shared_experts.up_proj.weight",
               "mlp.shared_experts.down_proj.weight"]
    return attn + mlp + ["input_layernorm.weight",
                         "post_attention_layernorm.weight"]


def forward_logits(weights, m: dict, ids, rows, mm: str = "f32",
                   experts_held=None, drop_expert=None):
    """Logits (len(rows), vocab), float32, of the full causal forward over
    ``ids`` (S,) at the positions ``rows``. ``weights`` is a mapping from
    leaf name to array, or a function of the name (one layer's leaves are
    asked for, used and let go before the next layer's). ``ids`` may be
    padded at its end: causal attention keeps padding out of earlier rows.
    ``drop_expert = (layer, expert)`` leaves that expert's output out."""
    get = weights if callable(weights) else weights.__getitem__
    ids = jnp.asarray(ids, jnp.int32)
    x = get("model.embed_tokens.weight")[ids].astype(F32)
    frozen = _freeze(m)
    for i in range(m["num_hidden_layers"]):
        lw = {leaf: get(f"model.layers.{i}.{leaf}")
              for leaf in layer_leaf_names(m, i)}
        drop = (drop_expert[1] if drop_expert is not None
                and drop_expert[0] == i else -1)
        x = _layer_jit(x, lw, jnp.int32(drop), m=frozen, mm=mm,
                       sparse=i >= m["first_k_dense_replace"],
                       experts_held=experts_held)
        del lw
    return _head_jit(x[jnp.asarray(rows)], get("model.norm.weight"),
                     get("lm_head.weight"), eps=m["rms_norm_eps"], mm=mm)
