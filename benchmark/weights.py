"""Seeded weights, made on the device in one jitted call, in the served type.

The benchmark makes the weights, not the program: the driver loads them into
the program's model, and the plain reference makes the same arrays again from
the same seed once the program's state is freed. Names are those of
``LlamaForCausalLM.named_parameters()``; a linear weight is ``(in, out)``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("self_attn.q_proj", "self_attn.k_proj", "self_attn.v_proj",
                "self_attn.o_proj", "mlp.gate_proj", "mlp.up_proj",
                "mlp.down_proj")


def layer_shapes(m: dict) -> dict:
    h, d = m["hidden_size"], m["head_dim"]
    q, kv, f = m["num_attention_heads"] * d, m["num_key_value_heads"] * d, \
        m["intermediate_size"]
    return {"self_attn.q_proj": (h, q), "self_attn.k_proj": (h, kv),
            "self_attn.v_proj": (h, kv), "self_attn.o_proj": (q, h),
            "mlp.gate_proj": (h, f), "mlp.up_proj": (h, f),
            "mlp.down_proj": (f, h)}


def shapes(m: dict) -> dict:
    """Every leaf's shape, in the model's own parameter order."""
    h, v = m["hidden_size"], m["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h)}
    for i in range(m["num_hidden_layers"]):
        for leaf, shp in layer_shapes(m).items():
            out[f"model.layers.{i}.{leaf}.weight"] = shp
        out[f"model.layers.{i}.input_layernorm.weight"] = (h,)
        out[f"model.layers.{i}.post_attention_layernorm.weight"] = (h,)
    out["model.norm.weight"] = (h,)
    if not m.get("tie_word_embeddings", False):
        out["lm_head.weight"] = (h, v)
    return out


def seed_key(seed: int):
    """A key from any whole number: ``--seed`` may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


@partial(jax.jit, static_argnames=("spec", "std", "dtype"))
def _make(key, spec, std, dtype):
    out = {}
    for i, (name, shape) in enumerate(spec):
        if len(shape) == 1:
            out[name] = jnp.ones(shape, dtype)
        else:
            out[name] = (std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """``{name: array}`` for the model section ``m`` of a configuration."""
    return _make(seed_key(seed), tuple(shapes(m).items()), float(std), dtype)


def count_params(m: dict) -> int:
    n = 0
    for shp in shapes(m).values():
        k = 1
        for s in shp:
            k *= s
        n += k
    return n
