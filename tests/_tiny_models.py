"""Tiny seeded models that more than one engine test file serves.

The engine's scheduler tests run over two model kinds: the dense LLaMA
block with a (kv heads, head size) page pool pair, and the sparse-expert /
latent-attention block (``models/moe_mla.py``) whose two pools have
different shapes on one table and whose decode segment carries an eighth
output, the routing counters.
"""
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import MoEMLAForCausalLM, moe_mla_tiny_config

MODEL_KINDS = ("dense", "sparse_latent")


def sparse_latent_model(**config_overrides):
    """``moe_mla_tiny_config()`` (hidden 64, 4 heads, latent 32, 16 experts
    top-4, 3 layers of which the first is dense) on seeded float32
    weights."""
    paddle.seed(27)
    m = MoEMLAForCausalLM(moe_mla_tiny_config(**config_overrides))
    m.eval()
    # a correction bias large enough to change choices, as a trained one is
    for name, p in m.named_parameters():
        if name.endswith("e_score_correction_bias"):
            p._value = 0.05 * jax.random.normal(
                jax.random.PRNGKey(len(name)), p._value.shape, jnp.float32)
    return m


def decode_assignments(model, decode_tokens):
    """What ``serving.moe_assignments_total`` reads after the engine has
    handed out ``decode_tokens`` tokens from decode segments: every live
    row of a step makes top-k assignments in each sparse layer, and a
    segment that was discarded, replayed or bisected is counted once, as
    consumed. 0 for a model that counts nothing."""
    if not getattr(model, "step_stat_names", ()):
        return 0
    cfg = model.config
    sparse_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    return cfg.num_experts_per_tok * sparse_layers * decode_tokens
