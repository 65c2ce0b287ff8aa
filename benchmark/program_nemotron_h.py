"""The program module of the ``nemotron_h`` hybrid model, which its
configuration file names under ``program``: the one place this configuration
touches the system under test. The serving stack is built by
``program.build_serving`` itself, and ``bag_extras`` hands the serving driver
what this model's readers need besides.

Importing the model is the first thing ``build_model`` does: on a checkout
that lacks it (the parent commit under this PR's benchmark files) the cell
ends there with an ImportError, at once.
"""
from __future__ import annotations

import gc

import jax.numpy as jnp

from benchmark import harness, program
from benchmark import weights_nemotron_h as W
from benchmark.program_retention import _turn_until_idle

# what the engine says of itself once built, for the per-layer readers, and
# the engine itself, for ``served_states`` (the driver lets go of its own
# names before it asks for the comparison)
ENGINE_FACTS: dict = {}
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
    "use_conv_bias", "time_step_min", "time_step_max", "time_step_floor",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts",
    "routed_scaling_factor", "norm_topk_prob", "layer_norm_epsilon")


def build_serving(model, config: dict):
    engine, frontend = program.build_serving(model, config)
    kv = engine.kv_stats()
    ENGINE_FACTS["state_bytes_per_slot"] = kv.get("state_bytes_per_slot")
    ENGINE_FACTS["kv_bytes_per_token"] = kv.get("bytes_per_token")
    ENGINE_FACTS["engine"] = engine
    return engine, frontend


def served_states(sample):
    """What each sampled request LEAVES in its slot: the engine the window
    ran on, idle now, serves the sample once more through the same programs
    (prompt, as many tokens as were served), and each request's state rows
    are read back before its slot is granted again. A request a
    ``{"tokens", "states"}`` (the tokens this serving gave; ``states`` a
    Mamba-2 layer a pair ``(S, carried convolution inputs)``), or None where
    it did not end ``ok`` in a slot of its own; None for all where there is
    no engine. The engine is let go."""
    engine = ENGINE_FACTS.pop("engine", None)
    if engine is None or not _turn_until_idle(engine):
        return None
    out = []
    for g0 in range(0, len(sample), engine.max_slots):
        group = sample[g0:g0 + engine.max_slots]
        reqs = [engine.submit(item["prompt"], len(item["tokens"]))
                for item in group]
        idle = _turn_until_idle(engine)
        own = len({r.slot for r in reqs}) == len(reqs)
        for r in reqs:
            ok = idle and own and r.status == "ok"
            out.append({"tokens": r.output(),
                        "states": engine.read_state(r.slot)} if ok else None)
    del engine
    gc.collect()
    return out


def model_section(config: dict) -> dict:
    """The configuration's model keys with the deployment's share of the
    routed experts beside them (weights, reference and costs read it)."""
    m = dict(config["model"])
    m["experts_held"] = tuple(config["deployment"].get(
        "experts_held", (0, m["n_routed_experts"])))
    m["router_calibration"] = config["assumed"]["router_calibration"]
    return m


def bag_extras(config: dict) -> dict:
    """What ``readers/nemotron_h.py`` reads beside the serving driver's
    bag."""
    return {"model": model_section(config),
            "state_bytes_per_slot": ENGINE_FACTS.get("state_bytes_per_slot"),
            "kv_bytes_per_token": ENGINE_FACTS.get("kv_bytes_per_token")}


def model_config(m: dict, positions: int):
    from paddle_tpu.models import NemotronHConfig

    return NemotronHConfig(
        **{k: m[k] for k in MODEL_KEYS}, max_position_embeddings=positions,
        experts_held=m.get("experts_held"))


def level_routers(model, m: dict, seed: int) -> dict:
    """Set each expert layer's correction bias to the one under which its
    router chooses every one of the ``n_routed_experts`` equally often over
    ``assumed.router_calibration``'s seeded tokens (``W.level_bias``), as
    the published model's bias is trained to: on seeded weights alone the
    choices fall on the held experts as the seed has it, and a cell's level
    follows the seed. The tokens go through the model's own layers without
    a cache, a layer a compiled call; a layer's router is levelled on what
    the layers before it, levelled already, hand it. The biases are kept in
    ``W.LEVELLED`` for the reference. Returns the largest and smallest load
    a layer under its bias, as shares of the level load."""
    import paddle_tpu as paddle

    cal = m["router_calibration"]
    ids = W.calibration_tokens(m, seed, cal["sequences"], cal["length"])
    found = {}
    with paddle.no_grad():
        x = model.model.embed_tokens(paddle.to_tensor(ids))
        for i, layer in enumerate(model.model.layers):
            if layer.kind == "E":
                u = layer.norm(x)._value.reshape(-1, m["hidden_size"])
                bias, loads = W.level_bias(
                    W.router_scores(u, layer.mixer.gate.weight._value),
                    m["num_experts_per_tok"])
                name = f"model.layers.{i}.mixer.e_score_correction_bias"
                W.LEVELLED[(seed, name)] = bias
                layer.mixer.e_score_correction_bias.set_value(bias)
                found[name] = (float(loads.max()), float(loads.min()))
            x = paddle.jit.to_static(layer)(x)
    return found


def build_model(config: dict, seed: int):
    """``NemotronHForCausalLM`` at the configuration's sizes, its parameters
    deferred and then set to the benchmark's seeded weights, its routers
    levelled."""
    import paddle_tpu as paddle
    from paddle_tpu.models import NemotronHForCausalLM

    m = model_section(config)
    dtype = config["deployment"]["dtype"]
    cfg = model_config(m, int(config["assumed"]["positions_used"]))
    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        with paddle.LazyGuard():
            model = NemotronHForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(before)
    program.load_weights(model, W.make_weights(m, seed, jnp.dtype(dtype)))
    model.eval()
    loads = level_routers(model, m, seed)
    harness.log("routers levelled, largest and smallest load a layer "
                "(1 = level): " + ", ".join(
                    f"{hi:.3f} {lo:.3f}" for hi, lo in loads.values()))
    return model
