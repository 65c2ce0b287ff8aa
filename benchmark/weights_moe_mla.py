"""Seeded weights for the sparse-expert / latent-attention configuration,
made on the device a leaf at a time, each from a key of its own.

Names and shapes are those of ``MoEMLAForCausalLM.named_parameters()``; a
linear weight is ``(in, out)``, the held experts are stacked ``(experts, in,
out)`` with gate and up side by side. A leaf's key is the seed's key folded
with a checksum of its NAME, so any one leaf can be made again alone: the
driver loads all of them into the program's model, and the plain reference
asks for one layer's leaves at a time once the program's state is freed (a
sparse layer at the published widths is 2.48 GB in bfloat16 and would be
4.96 GB in float32; nothing here ever holds a float32 copy of more than one
expert). Matrices are normal(0, std); the router's correction bias is
normal(0, std) too, in float32, so that it changes choices as a trained one
does; norm weights are 1.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

BIAS = "e_score_correction_bias"


def held(m: dict) -> int:
    """Routed experts this chip holds (all of them unless the deployment
    says otherwise)."""
    return int(m.get("experts_held", (0, m["n_routed_experts"]))[1])


def attn_shapes(m: dict) -> dict:
    h, heads = m["hidden_size"], m["num_attention_heads"]
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return {"q_a_proj.weight": (h, ql), "q_a_layernorm.weight": (ql,),
            "q_b_proj.weight": (ql, heads * (nope + rope)),
            "kv_a_proj_with_mqa.weight": (h, kl + rope),
            "kv_a_layernorm.weight": (kl,),
            "kv_b_proj.weight": (kl, heads * (nope + vd)),
            "o_proj.weight": (heads * vd, h)}


def mlp_shapes(m: dict, i: int) -> dict:
    h = m["hidden_size"]
    if i < m["first_k_dense_replace"]:
        f = m["intermediate_size"]
        return {"gate_proj.weight": (h, f), "up_proj.weight": (h, f),
                "down_proj.weight": (f, h)}
    f, e = m["moe_intermediate_size"], m["n_routed_experts"]
    fs = f * m["n_shared_experts"]
    return {BIAS: (e,), "experts_gate_up": (held(m), h, 2 * f),
            "experts_down": (held(m), f, h), "gate.weight": (h, e),
            "shared_experts.gate_proj.weight": (h, fs),
            "shared_experts.up_proj.weight": (h, fs),
            "shared_experts.down_proj.weight": (fs, h)}


def shapes(m: dict) -> dict:
    """Every leaf's shape, in the model's own parameter order."""
    h, v = m["hidden_size"], m["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h)}
    for i in range(m["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        for leaf, shp in attn_shapes(m).items():
            out[pre + "self_attn." + leaf] = shp
        for leaf, shp in mlp_shapes(m, i).items():
            out[pre + "mlp." + leaf] = shp
        out[pre + "input_layernorm.weight"] = (h,)
        out[pre + "post_attention_layernorm.weight"] = (h,)
    out["model.norm.weight"] = (h,)
    out["lm_head.weight"] = (h, v)
    return out


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    if len(shape) == 3:      # stacked experts: one at a time, so that no
        # float32 copy of the whole stack ever exists
        return jax.lax.map(
            lambda e: (std * jax.random.normal(
                jax.random.fold_in(key, e), shape[1:], jnp.float32)
            ).astype(dtype), jnp.arange(shape[0]))
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def leaf(m: dict, seed: int, name: str, dtype=jnp.bfloat16,
         std: float = 0.02):
    """One leaf by name, the same array whenever and wherever asked."""
    shape = shapes(m)[name]
    if name.endswith(BIAS):
        dtype = jnp.float32
    elif len(shape) == 1:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(seed_key(seed), zlib.crc32(name.encode()))
    return _normal(key, tuple(shape), float(std), jnp.dtype(dtype))


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """``{name: array}`` for the whole model, for the program."""
    return {name: leaf(m, seed, name, dtype, std) for name in shapes(m)}


def provider(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """A function of the leaf's name, for the reference."""
    return lambda name: leaf(m, seed, name, dtype, std)


def count_params(m: dict, routed: bool = True) -> int:
    """Parameters held here; ``routed`` False leaves the routed experts
    out (what every token reads whatever it chose)."""
    n = 0
    for name, shp in shapes(m).items():
        if not routed and ".experts_" in name:
            continue
        k = 1
        for s in shp:
            k *= s
        n += k
    return n
