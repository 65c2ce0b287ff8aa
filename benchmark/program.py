"""The one place the benchmark touches the system under test.

Builds the program's model from a configuration file with the benchmark's
seeded weights, and its serving stack or train step through the program's
public entry points. Everything the benchmark measures with (traffic, stamps,
costs, reference, comparison) lives elsewhere under ``benchmark/``.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import weights as W


def llama_config(m: dict, positions: int, recompute: bool = False):
    from paddle_tpu.models import LlamaConfig

    if m["hidden_size"] != m["num_attention_heads"] * m["head_dim"]:
        raise ValueError("models/llama.py derives head_dim from hidden_size")
    return LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=positions, rms_norm_eps=m["rms_norm_eps"],
        rope_theta=float(m["rope_theta"]),
        tie_word_embeddings=m["tie_word_embeddings"],
        use_recompute=recompute)


def build_model(config: dict, seed: int, recompute: bool = False):
    """``LlamaForCausalLM`` at the configuration's sizes, its parameters
    deferred (``LazyGuard``) and then set to the benchmark's seeded weights:
    nothing is initialised twice and nothing on the host."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    m = config["model"]
    dtype = config["deployment"]["dtype"]
    cfg = llama_config(m, int(config["assumed"]["positions_used"]), recompute)
    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        with paddle.LazyGuard():
            model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(before)
    load_weights(model, W.make_weights(m, seed, jnp.dtype(dtype)))
    return model


def load_weights(model, weights: dict):
    params = dict(model.named_parameters())
    if set(params) != set(weights):
        raise ValueError(f"weight names differ from the model's: "
                         f"{sorted(set(params) ^ set(weights))[:4]}")
    model.load_raw_state(weights)
    for p in params.values():
        p._lazy_init = None      # set: nothing is left to materialise


def build_serving(model, config: dict):
    """(engine, frontend) as the configuration's deployment block says."""
    from paddle_tpu.models.frontend import ServingFrontend
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    e = config["deployment"]["engine"]
    model.eval()
    engine = ContinuousBatchingEngine(
        model, max_slots=e["max_slots"], max_len=e["max_len"],
        page_size=e["page_size"], prompt_buckets=tuple(e["prompt_buckets"]),
        pool_pages=e["pool_pages"], do_sample=e["do_sample"])
    frontend = ServingFrontend(
        engine, segment=e["segment"],
        max_queue=config["deployment"]["frontend"]["max_queue"])
    return engine, frontend


def build_train_step(model, config: dict):
    """``jit.TrainStep`` over AdamW as the configuration's train block says,
    on one chip (a fleet mesh comes with the cell that needs one)."""
    import paddle_tpu as paddle

    t = config["deployment"]["train"]
    if t["mesh"]:
        raise ValueError("no cell trains over a mesh yet: PERF.md, Open "
                         "questions")
    model.train()
    opt = paddle.optimizer.AdamW(
        learning_rate=t["lr"], beta1=t["beta1"], beta2=t["beta2"],
        epsilon=t["epsilon"], weight_decay=t["weight_decay"],
        parameters=model.parameters(), multi_precision=t["multi_precision"],
        acc_dtype=t["moment_dtype"])
    return paddle.jit.TrainStep(model, lambda loss: loss, opt)


def feed(ids):
    """One batch as the step takes it: the seam at which the rehearsal's
    tests plant a batch with rows left out."""
    return ids
