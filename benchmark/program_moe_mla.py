"""The program module of the sparse-expert / latent-attention model, which its
configuration file names under ``program``: the one place this configuration
touches the system under test. The serving stack is built by
``program.build_serving`` itself, and ``bag_extras`` hands the serving driver
what this model's readers need besides.

Importing the model is the first thing ``build_model`` does: on a checkout
that lacks it (the parent commit under this PR's benchmark files) the cell
ends there with an ImportError, at once.
"""
from __future__ import annotations

import jax.numpy as jnp

from benchmark import program
from benchmark import weights_moe_mla as W

# what the engine says of itself once built, for the per-layer readers
# (the driver puts it in the bag; the engine is gone by then)
ENGINE_FACTS: dict = {}


def build_serving(model, config: dict):
    engine, frontend = program.build_serving(model, config)
    ENGINE_FACTS["kv_bytes_per_token"] = \
        engine.kv_stats()["bytes_per_token"]
    return engine, frontend


def bag_extras(config: dict) -> dict:
    """What ``readers/moe_mla.py`` reads beside the serving driver's bag."""
    return {"model": model_section(config),
            "kv_bytes_per_token": ENGINE_FACTS["kv_bytes_per_token"]}


def model_config(m: dict, positions: int, experts_held=None):
    from paddle_tpu.models import MoEMLAConfig

    return MoEMLAConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        n_routed_experts=m["n_routed_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        first_k_dense_replace=m["first_k_dense_replace"],
        routed_scaling_factor=m["routed_scaling_factor"],
        norm_topk_prob=m["norm_topk_prob"],
        max_position_embeddings=positions, rms_norm_eps=m["rms_norm_eps"],
        rope_theta=float(m["rope_theta"]), experts_held=experts_held)


def model_section(config: dict) -> dict:
    """The configuration's model keys with the deployment's share of the
    routed experts beside them (weights, reference and costs read it)."""
    m = dict(config["model"])
    m["experts_held"] = tuple(config["deployment"].get(
        "experts_held", (0, m["n_routed_experts"])))
    return m


def build_model(config: dict, seed: int):
    """``MoEMLAForCausalLM`` at the configuration's sizes, its parameters
    deferred and then set to the benchmark's seeded weights."""
    import paddle_tpu as paddle
    from paddle_tpu.models import MoEMLAForCausalLM

    m = model_section(config)
    dtype = config["deployment"]["dtype"]
    cfg = model_config(m, int(config["assumed"]["positions_used"]),
                       m["experts_held"])
    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        with paddle.LazyGuard():
            model = MoEMLAForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(before)
    program.load_weights(model, W.make_weights(m, seed, jnp.dtype(dtype)))
    return model
