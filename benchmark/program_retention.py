"""The program module of the power-retention model, which its configuration
file names under ``program``: the one place this configuration touches the
system under test. The serving stack is built by ``program.build_serving``
itself, and ``bag_extras`` hands the serving driver what this model's readers
need besides.

Importing the model is the first thing ``build_model`` does: on a checkout
that lacks it (the parent commit under this PR's benchmark files) the cell
ends there with an ImportError, at once.
"""
from __future__ import annotations

import gc
import time

import jax.numpy as jnp

from benchmark import program
from benchmark import weights_retention as W

# what the engine says of itself once built, for the per-layer readers, and
# the engine itself, for ``served_states`` (the driver lets go of its own
# names before it asks for the comparison)
ENGINE_FACTS: dict = {}
DRAIN_LIMIT_S = 60.0


def build_serving(model, config: dict):
    engine, frontend = program.build_serving(model, config)
    ENGINE_FACTS["state_bytes_per_slot"] = \
        engine.kv_stats().get("state_bytes_per_slot")
    ENGINE_FACTS["engine"] = engine
    return engine, frontend


def _turn_until_idle(engine):
    give_up = time.monotonic() + DRAIN_LIMIT_S
    while engine.has_work() and time.monotonic() < give_up:
        engine.step()
    return not engine.has_work()


def served_states(sample):
    """What each sampled request LEAVES in its slot: the engine the window
    ran on, idle now, serves the sample once more through the same programs
    (prompt, as many tokens as were served), and each request's state rows
    are read back before its slot is granted again. A request a
    ``{"tokens", "states"}`` (the tokens this serving gave; ``states`` a
    layer a pair over the whole outer product, ``ops/pallas/retention.
    dense_state``), or None where it did not end ``ok`` in a slot of its
    own; None for all where there is no engine. The engine is let go."""
    from paddle_tpu.ops.pallas.retention import dense_state

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
            out.append({"tokens": r.output(), "states": [
                dense_state(s, z) for s, z in engine.read_state(r.slot)]}
                if ok else None)
    del engine
    gc.collect()
    return out


def model_section(config: dict) -> dict:
    """The configuration's model keys with the assumed range of the gate's
    bias beside them (weights and reference read it)."""
    m = dict(config["model"])
    m["gate_bias_range"] = tuple(config["assumed"]["gate_bias_range"])
    return m


def bag_extras(config: dict) -> dict:
    """What ``readers/retention.py`` reads beside the serving driver's
    bag."""
    return {"model": model_section(config),
            "state_bytes_per_slot": ENGINE_FACTS.get("state_bytes_per_slot")}


def model_config(m: dict, positions: int):
    from paddle_tpu.models import PowerRetentionConfig

    return PowerRetentionConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        max_position_embeddings=positions, rms_norm_eps=m["rms_norm_eps"],
        rope_theta=float(m["rope_theta"]))


def build_model(config: dict, seed: int):
    """``PowerRetentionForCausalLM`` at the configuration's sizes, its
    parameters deferred and then set to the benchmark's seeded weights."""
    import paddle_tpu as paddle
    from paddle_tpu.models import PowerRetentionForCausalLM

    m = model_section(config)
    dtype = config["deployment"]["dtype"]
    cfg = model_config(m, int(config["assumed"]["positions_used"]))
    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        with paddle.LazyGuard():
            model = PowerRetentionForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(before)
    program.load_weights(model, W.make_weights(m, seed, jnp.dtype(dtype)))
    return model
