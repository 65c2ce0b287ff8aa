"""Seeded weights for the power-retention configuration, made on the device
in one jitted call, each leaf from a key of its own.

Names and shapes are those of ``PowerRetentionForCausalLM.named_parameters()``;
a linear weight is ``(in, out)``. A leaf's key is the seed's key folded with a
checksum of its NAME, so any one leaf can be made again alone: the driver loads
all of them into the program's model, and the plain reference asks for one
layer's leaves at a time once the program's state is freed. Matrices are
normal(0, std) in the served type; norm weights are 1; the gate's bias is
uniform in the configuration's ``gate_bias_range`` ([4, 8]) in float32, so
that the gate lies in (0.982, 0.9997) and the state remembers: with a
zero-mean gate the state would forget in twenty tokens and no comparison
could see a fault in what is carried.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

GATE_BIAS = "self_attn.g_bias"


def layer_shapes(m: dict) -> dict:
    h, d = m["hidden_size"], m["head_dim"]
    q, kv, f = (m["num_attention_heads"] * d, m["num_key_value_heads"] * d,
                m["intermediate_size"])
    return {"self_attn.q_proj.weight": (h, q),
            "self_attn.k_proj.weight": (h, kv),
            "self_attn.v_proj.weight": (h, kv),
            "self_attn.o_proj.weight": (q, h),
            "self_attn.q_norm.weight": (d,), "self_attn.k_norm.weight": (d,),
            "self_attn.g_proj.weight": (h, m["num_key_value_heads"]),
            GATE_BIAS: (m["num_key_value_heads"],),
            "mlp.gate_proj.weight": (h, f), "mlp.up_proj.weight": (h, f),
            "mlp.down_proj.weight": (f, h),
            "input_layernorm.weight": (h,),
            "post_attention_layernorm.weight": (h,)}


def shapes(m: dict) -> dict:
    """Every leaf's shape, in the model's own parameter order."""
    h, v = m["hidden_size"], m["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h)}
    for i in range(m["num_hidden_layers"]):
        for leaf, shp in layer_shapes(m).items():
            out[f"model.layers.{i}.{leaf}"] = shp
    out["model.norm.weight"] = (h,)
    out["lm_head.weight"] = (h, v)
    return out


def _leaf(key, name, shape, std, dtype, bias_range):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if name.endswith(GATE_BIAS):
        return jax.random.uniform(key, shape, jnp.float32, *bias_range)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@partial(jax.jit, static_argnames=("spec", "std", "dtype", "bias_range"))
def _make(key, spec, std, dtype, bias_range):
    return {name: _leaf(key, name, shape, std, dtype, bias_range)
            for name, shape in spec}


def bias_range(m: dict) -> tuple:
    return tuple(float(x) for x in m.get("gate_bias_range", (4.0, 8.0)))


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02,
                 names=None):
    """``{name: array}`` for the whole model (or for ``names``), in one
    jitted call."""
    spec = tuple((n, s) for n, s in shapes(m).items()
                 if names is None or n in names)
    return _make(seed_key(seed), spec, float(std), jnp.dtype(dtype),
                 bias_range(m))


def provider(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """A function of the leaf's name, for the reference: a layer's leaves
    are made together the first time one of them is asked for, and let go
    when the next layer's are."""
    held: dict = {}

    def get(name):
        if name not in held:
            held.clear()
            parts = name.split(".")
            prefix = ".".join(parts[:3]) + "." if parts[1] == "layers" \
                else name
            held.update(make_weights(
                m, seed, dtype, std,
                names=frozenset(n for n in shapes(m)
                                if n.startswith(prefix))))
        return held[name]

    return get


def count_params(m: dict) -> int:
    n = 0
    for shp in shapes(m).values():
        k = 1
        for s in shp:
            k *= s
        n += k
    return n
