"""Seeded weights for the ``nemotron_h`` configuration, made on the device a
leaf a jitted call, each leaf from a key of its own.

Names and shapes are those of ``NemotronHForCausalLM.named_parameters()``; a
linear weight is ``(in, out)``. A leaf's key is the seed's key folded with a
checksum of its NAME, so any one leaf can be made again alone: the driver
loads all of them into the program's model, and the plain reference asks for
one layer's leaves at a time once the program's state is freed. Matrices are
normal(0, std) in the served type; norm weights and ``D`` are 1; ``A_log`` is
the log of a uniform draw in [1, 16] and ``dt_bias`` the inverse softplus of a
log-uniform draw in [``time_step_min``, ``time_step_max``] floored at
``time_step_floor``, both float32: the family's own initialisation, which
spreads a head's memory from a few tokens to a thousand, so that a fault in
what is carried can be seen. The router's correction bias (float32) is what
the published model trains it to be, the bias under which every expert is
chosen equally often: it is zero until ``program_nemotron_h.level_routers``
has levelled this seed's routers over ``assumed.router_calibration``'s seeded
tokens (:func:`level_bias`; kept in ``LEVELLED``, so the reference, built
later in the same process, gets what the program serves with). The first
expert stack's columns past the
expert's width (1,856 of 1,920: the program pads them to a lane multiple) are
drawn like the rest; the grouped product multiplies them and their columns of
its result are sliced away, so no number of the model depends on them.
"""
from __future__ import annotations

import functools
import math
import zlib

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key
# normal(0, std) a leaf, a stacked leaf an expert at a time
from benchmark.weights_moe_mla import _normal

F32_LEAVES = ("e_score_correction_bias", "A_log", "D", "dt_bias")
# the levelled correction biases, {(seed, leaf name): (experts,) float32}
LEVELLED: dict = {}


def held(m: dict) -> int:
    return int(m.get("experts_held", (0, m["n_routed_experts"]))[1])


def layer_shapes(m: dict, kind: str) -> dict:
    h = m["hidden_size"]
    if kind == "M":
        heads, p = m["mamba_num_heads"], m["mamba_head_dim"]
        inner = heads * p
        conv = inner + 2 * m["n_groups"] * m["ssm_state_size"]
        return {"norm.weight": (h,),
                "mixer.conv_weight": (m["conv_kernel"], conv),
                "mixer.conv_bias": (conv,), "mixer.A_log": (heads,),
                "mixer.D": (heads,), "mixer.dt_bias": (heads,),
                "mixer.norm_weight": (inner,),
                "mixer.in_proj.weight": (h, inner + conv + heads),
                "mixer.out_proj.weight": (inner, h)}
    if kind == "*":
        d = m["head_dim"]
        q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
        return {"norm.weight": (h,), "mixer.q_proj.weight": (h, q),
                "mixer.k_proj.weight": (h, kv),
                "mixer.v_proj.weight": (h, kv),
                "mixer.o_proj.weight": (q, h)}
    f, e = m["moe_intermediate_size"], m["n_routed_experts"]
    fs = m["moe_shared_expert_intermediate_size"]
    return {"norm.weight": (h,), "mixer.e_score_correction_bias": (e,),
            "mixer.experts_up": (held(m), h, -(-f // 128) * 128),
            "mixer.experts_down": (held(m), f, h),
            "mixer.gate.weight": (h, e),
            "mixer.shared_experts.up_proj.weight": (h, fs),
            "mixer.shared_experts.down_proj.weight": (fs, h)}


def shapes(m: dict) -> dict:
    """Every leaf's shape, in the model's own parameter order."""
    h, v = m["hidden_size"], m["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h)}
    for i, kind in enumerate(m["hybrid_override_pattern"]):
        for leaf_name, shp in layer_shapes(m, kind).items():
            out[f"model.layers.{i}.{leaf_name}"] = shp
    out["model.norm.weight"] = (h,)
    out["lm_head.weight"] = (h, v)
    return out


def leaf(m: dict, seed: int, name: str, dtype=jnp.bfloat16,
         std: float = 0.02):
    """One leaf by name, the same array whenever and wherever asked."""
    shape = tuple(shapes(m)[name])
    key = jax.random.fold_in(seed_key(seed), zlib.crc32(name.encode()))
    last = name.rsplit(".", 1)[-1]
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if last == "dt_bias":
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(m["time_step_min"]),
            math.log(m["time_step_max"]))), m["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if last == "D":
        return jnp.ones(shape, jnp.float32)
    if last == "e_score_correction_bias":
        return LEVELLED.get((seed, name), jnp.zeros(shape, jnp.float32))
    if last == "conv_bias":
        return jnp.zeros(shape, dtype)
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    return _normal(key, shape, float(std), jnp.dtype(dtype))


@jax.jit
def router_scores(u, gate_w):
    """``sigmoid(u W_g)`` in float32, as the model's router has it."""
    return jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))


@functools.partial(jax.jit, static_argnames=("top_k", "steps"))
def level_bias(scores, top_k: int, steps: int = 400):
    """The correction bias under which every expert is among the ``top_k``
    of ``scores + bias`` for the same number of rows: ``scores`` (rows,
    experts) are the router's sigmoids. The family trains its bias to this
    end by the same rule (an expert chosen too often is moved down, too
    seldom up); here the step follows the miss and shrinks, 0.05 to 0.0005
    of a score. Returns ``(bias, loads)``, the loads as shares of the level
    load under the bias returned."""
    rows, experts = scores.shape
    level = rows * top_k / experts

    def loads(bias):
        _, ids = jax.lax.top_k(scores + bias, top_k)
        return jnp.zeros(experts, jnp.float32).at[ids.reshape(-1)].add(
            1.0 / level)

    def step(i, bias):
        rate = 0.05 * 0.01 ** (i / (steps - 1))
        return bias - rate * (loads(bias) - 1.0)

    bias = jax.lax.fori_loop(0, steps, step,
                             jnp.zeros(experts, jnp.float32))
    return bias, loads(bias)


def calibration_tokens(m: dict, seed: int, sequences: int, length: int):
    """The seeded token ids the routers are levelled over: uniform over the
    whole vocabulary, as the traffic's prompts are."""
    key = jax.random.fold_in(seed_key(seed), zlib.crc32(b"router_calibration"))
    return jax.random.randint(key, (sequences, length), 0, m["vocab_size"],
                              jnp.int32)


def make_weights(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """``{name: array}`` for the whole model, for the program."""
    return {name: leaf(m, seed, name, dtype, std) for name in shapes(m)}


def provider(m: dict, seed: int, dtype=jnp.bfloat16, std: float = 0.02):
    """A function of the leaf's name, for the reference: after the program,
    in its process, which levels the routers."""
    if not any(key[0] == seed for key in LEVELLED):
        raise RuntimeError(
            f"the routers of seed {seed} were not levelled in this process "
            "(program_nemotron_h.level_routers): the reference would choose "
            "its experts under another bias than the program")
    return lambda name: leaf(m, seed, name, dtype, std)


def count_params(m: dict, routed: bool = True) -> int:
    """Parameters held here, the first stack at the expert's own width;
    ``routed`` False leaves the routed experts out (what every token reads
    whatever it chose)."""
    n = 0
    for name, shp in shapes(m).items():
        if ".experts_" in name:
            if not routed:
                continue
            if name.endswith("experts_up"):
                shp = shp[:2] + (m["moe_intermediate_size"],)
        n += math.prod(shp)
    return n
