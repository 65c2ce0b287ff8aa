"""A tiny CPU rehearsal of each driver's control flow, and the comparison
that decides ``correct`` shown to fail: under the lower-precision control and
under each fault the cells can have, planted beneath the timed path. Nothing
here is a device metric and none is printed as one."""
import importlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import compare, harness, program
from benchmark import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# set from this tiny size's own readings on the CPU (bf16 program: losses
# 1-2e-5, gradient 8e-4, change 1.6e-3; fp8 control: losses 1e-4, gradient
# 6e-3; half of the batch: losses > 1e-3; state unchanged: change 1)
TRAIN_LIMITS = {k: {"limit": v} for k, v in {
    "loss1_gap": 5e-5, "loss2_gap": 5e-5, "loss3_gap": 5e-5, "loss_gap_max": 5e-5,
    "grad_norm_gap": 3e-3, "change_norm_gap": 0.02}.items()}
SERVE_LIMITS = {"logit_gap_max": {"limit": 1e-3}}


def data(name):
    with open(os.path.join(HERE, "data", name + ".json")) as f:
        return json.load(f)


def loaded(config, traffic, limits):
    e2e = [{"name": n, "unit": "x", "better": "lower", "bound": 0.1,
            "source": "host_clock"} for n in ("setup_s",)]
    return {"bench": {"end_to_end": e2e, "per_layer": []}, "cell": {"chips": 1},
            "config": data(config), "traffic": data(traffic), "limits": limits}


def run(config, traffic, limits, seed, seconds=1.5, control=None):
    """Everything of a run but the harness's look for a chip."""
    import paddle_tpu as paddle

    # one process runs one cell; here several share one, and the program's
    # global RNG keeps a tracer of an engine's trace that a later TrainStep
    # trace trips over (PERF.md, Open questions): start each from a fresh key
    paddle.seed(0)
    return harness.run_cell(loaded(config, traffic, limits), "rehearsal",
                            seed, seconds, False, time.monotonic(), dict(CPU),
                            control=control)


def drive(config, traffic, seed):
    """A driver's own bag, as it hands it to the harness."""
    import paddle_tpu as paddle

    paddle.seed(0)
    traffic = data(traffic)
    driver = importlib.import_module(traffic["driver"])
    ctx = {"workload": "rehearsal", "seed": seed, "seconds": 1.5,
           "trace": False, "t_process": time.monotonic(), "device": dict(CPU),
           "chips": 1, "config": data(config), "traffic": traffic,
           "limits": {}, "workdir": os.path.join(harness.ROOT, ".bench_tmp"),
           "log": lambda msg: None, "control": None}
    return driver.run(ctx)


def test_serving_rehearsal_is_correct_and_prints_no_device_metric():
    r = run("tiny-serve", "tiny-chat", SERVE_LIMITS, 2 ** 31 + 5)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 5
    assert set(r["metrics"]) == {"setup_s"} and r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "compared"
    assert r["compared"]["logit_gap_max"]["value"] <= 1e-3
    assert r["notes"]["compiles_in_window"] == 0
    assert r["notes"]["reference"]["requests"] == 3


# what the configuration's program module adds to the serving driver's bag
# (PR 37 folded the three twin drivers back into ``drivers/serve.py``; these
# are the keys and values each twin added): the model's keys with the
# deployment's share beside them, the pool's bytes a token (latent: 3 layers
# x (32 + 8) x 4; hybrid: 1 attention layer x 2 x 2 KV heads x 16 x 4) and
# the state's bytes a slot (retention: 2 layers as the engine lays them out;
# hybrid: 2 Mamba-2 layers x (4 x 8 x 16 + 3 x 96) x 4)
@pytest.mark.parametrize("config,traffic,program,extras", [
    ("tiny-serve", "tiny-chat", "benchmark.program", {}),
    ("tiny-moe-mla", "tiny-reason", "benchmark.program_moe_mla",
     {"kv_bytes_per_token": 480}),
    ("tiny-retention", "tiny-continue", "benchmark.program_retention",
     {"state_bytes_per_slot": 39168}),
    ("tiny-nemotron-h", "tiny-think", "benchmark.program_nemotron_h",
     {"kv_bytes_per_token": 256, "state_bytes_per_slot": 6400})])
def test_the_serving_driver_adds_what_the_configurations_program_gives(
        config, traffic, program, extras):
    bag = drive(config, traffic, 2 ** 31 + 31)
    section = getattr(importlib.import_module(program), "model_section",
                      lambda c: c["model"])
    assert bag["model"] == section(data(config))
    for key in ("kv_bytes_per_token", "state_bytes_per_slot"):
        assert bag.get(key) == extras.get(key), key
        assert (key in bag) == (key in extras), key
    assert bag["failed"] == 0 and harness.decide(bag["checks"][-1:])


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from paddle_tpu.models import serving

    honest = serving.Request.output

    def altered(self):
        out = np.array(honest(self))
        if len(out) > 2:
            out[2] = (out[2] + 1) % 256
        return out

    monkeypatch.setattr(serving.Request, "output", altered)
    r = run("tiny-serve", "tiny-chat", SERVE_LIMITS, 7)
    assert not r["correct"]
    assert r["compared"]["logit_gap_max"]["value"] > 1e-3


def test_a_missing_limit_or_an_unfinished_sample_is_not_correct():
    assert not harness.decide([("x", 0.1, None)])
    assert not harness.decide([("x", None, 1.0)])
    assert not harness.decide([("x", float("nan"), 1.0)])
    assert not harness.decide([])
    assert harness.decide([("x", 0.0, 0.0), ("y", 0.5, 1.0)])


def test_training_rehearsal_is_correct():
    r = run("tiny-train", "tiny-pack", TRAIN_LIMITS, 11)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] >= 3
    assert set(r["metrics"]) == {"setup_s"}
    assert r["notes"]["reference"]["leaves_left_out"] == 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import paddle_tpu as paddle

    honest = paddle.jit.TrainStep.__call__

    def frozen(self, *args, **kwargs):
        accs, masters = self._accs, self._masters
        keep = ({k: v + 0 for k, v in accs.items()},
                {k: v + 0 for k, v in masters.items()})
        loss = honest(self, *args, **kwargs)
        self._accs, self._masters = keep
        return loss

    monkeypatch.setattr(paddle.jit.TrainStep, "__call__", frozen)
    r = run("tiny-train", "tiny-pack", TRAIN_LIMITS, 12)
    assert not r["correct"]
    # nothing moved: the change reads 1 by the worst-leaf measure
    assert r["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-3)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    honest = program.feed
    monkeypatch.setattr(program, "feed",
                        lambda ids: honest(ids[: ids.shape[0] // 2]))
    r = run("tiny-train", "tiny-pack", TRAIN_LIMITS, 13)
    assert not r["correct"]
    assert r["compared"]["loss1_gap"]["value"] > 5e-5


@pytest.mark.parametrize("config,traffic,limits,seed,names", [
    ("tiny-train", "tiny-pack", TRAIN_LIMITS, 16, {"fp8", "half_batch"}),
    ("tiny-serve", "tiny-chat", SERVE_LIMITS, 17, {"fp8"})])
def test_the_control_tool_judges_each_control_not_correct(
        config, traffic, limits, seed, names):
    """``tools/control.py`` as it runs on the chip, but for the look for
    one: the program is correct, and every control read in the same run
    fails the same limits through the same ``decide``."""
    from benchmark.tools import control

    r = run(config, traffic, limits, seed, control="fp8")
    assert r["correct"], r["compared"]
    judged = control.judge(r["notes"]["reference"], limits)
    assert set(judged) == names
    assert not any(c["correct"] for c in judged.values()), judged


def test_a_number_named_not_compared_is_left_out_and_an_unnamed_one_fails():
    limits = {"a": {"limit": 1.0}, "b": {"limit": None, "not_compared": "why"}}
    checks = compare.checks_of({"a": 0.5, "b": 9.0}, limits)
    assert checks == [("a", 0.5, 1.0)] and harness.decide(checks)
    assert not harness.decide(compare.checks_of({"a": 0.5, "c": 0.0}, limits))
    assert not harness.decide(compare.checks_of({"a": float("inf")}, limits))
    # the worst-leaf measure and its plain form beside it
    want = {"x": 1.0, "y": 1.0, "z": 1e-3}
    got = {"x": 1.0, "y": 1.01, "z": 2e-3}
    assert compare.worst_leaf_gap(got, want) == pytest.approx(0.01)
    assert compare.worst_leaf_gap(got, want, floor=False) == pytest.approx(1.0)


def test_the_lower_precision_control_is_not_correct():
    """The reference put in the program's place, computed in fp8 (the nearest
    precision below bfloat16), fails the limits the program passes."""
    config, traffic = data("tiny-train"), data("tiny-pack")
    want = compare.reference_training(config, traffic, 14)
    low = compare.reference_training(config, traffic, 14, mm="fp8")
    numbers = compare.training_numbers(low, want)
    checks = [(k, numbers[k], v["limit"]) for k, v in TRAIN_LIMITS.items()]
    assert not harness.decide(checks), numbers
    # and for serving: the token fp8 puts first lies below the reference's best
    sconf = data("tiny-serve")
    m = sconf["model"]
    weights = W.make_weights(m, 15, np.float32)
    rng = np.random.default_rng(15)
    sample = [{"prompt": rng.integers(0, 256, 40, dtype=np.int32),
               "tokens": rng.integers(0, 256, 30, dtype=np.int32)} for _ in range(3)]
    _, c_gap, n = compare.serving_gaps(weights, m, sample, control_mm="fp8")
    assert n == 90 and c_gap > SERVE_LIMITS["logit_gap_max"]["limit"]
