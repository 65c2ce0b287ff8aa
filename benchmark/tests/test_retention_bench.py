"""The power-retention configuration's harness pieces at a tiny size on the
CPU: the serving driver's control flow, the per-leaf weights, the costs
against hand counts, the readers on hand-made bags, and the comparison that
decides ``correct`` shown to fail under the float8 control and under the
planted fault "the carried state dropped at every chunk boundary". Nothing
here is a device metric."""
import json
import os

import numpy as np
import pytest

from benchmark import costs_retention as C
from benchmark import program_retention
from benchmark import weights_retention as W
from benchmark.readers import retention as R
from benchmark.tests.test_rehearsal import data, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# this tiny size's own readings on the CPU, float32 program: under 1e-5;
# float8 control 0.02-0.2; the state dropped at chunk boundaries 0.01-0.2
LIMITS = {"logit_gap_max": {"limit": 1e-3}, "logit_gap_mean": {"limit": 1e-4},
          # the state a request leaves, against the reference's float32 sum:
          # under 1e-6 here; kept in bfloat16 6e-3 to 9e-3
          **{name: {"limit": 1e-4} for name in (
              "state_s_gap", "state_z_gap", "state_s_gap_first",
              "state_z_gap_first")}}
DEC = "^power_retention_decode[\\w.\\-]* = .*custom-call\\("
CHUNK = "^power_retention_chunk[\\w.\\-]* = .*custom-call\\("
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "brumby-14b-base-d6.json")) as f:
        return json.load(f)


def test_the_configuration_file_holds_the_published_numbers_twice_alike():
    body = published()
    for key, value in body["model"].items():
        if key not in ("_what", "architectures"):
            assert body[key] == value, key
    assert body["reduced"] == ["num_hidden_layers"]
    assert body["published"] == {"num_hidden_layers": 40}
    assert body["num_hidden_layers"] == 6
    assert body["deployment"]["chips_per_layer"] == 1
    assert body["deployment"]["engine"]["max_slots"] == 32
    for key in ("power", "gate", "normalisation", "qk_norm_and_rotary",
                "state_dtype", "weights", "gate_bias"):
        assert body["assumed"][key]
    m = program_retention.model_section(body)
    assert m["gate_bias_range"] == (4.0, 8.0)
    assert W.count_params(m) == body["params"] == 3537947184


def test_the_traffic_file_is_three_quarters_of_its_recorded_knee():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "continue-open.json")) as f:
        t = json.load(f)
    assert t["requests_per_block"] == int(0.75 * t["knee_rps"] * t["block_s"])
    assert t["prompt"] == {"dist": "lognormal", "median": 512, "sigma": 0.9,
                           "min": 64, "max": 3072}
    assert t["output"] == {"dist": "lognormal", "median": 320, "sigma": 0.5,
                           "min": 96, "max": 1024}
    assert (t["block_s"], t["ramp_s"], t["pairing_seed"],
            t["check_requests"]) == (5, 20, 31, 6)


def test_the_cells_limits_lie_between_their_two_readings_with_room():
    """Each compared number's limit between the program's largest reading
    and its control's smallest, at least twice from either (``_readings`` /
    ``_state_readings`` say which runs)."""
    with open(os.path.join(ROOT, "benchmark", "limits",
                           "brumby14b-continue-open.json")) as f:
        limits = json.load(f)
    compared = {k: v for k, v in limits.items() if not k.startswith("_")}
    assert set(compared) == {"logit_gap_max", "logit_gap_mean",
                             "state_s_gap", "state_z_gap",
                             "state_s_gap_first", "state_z_gap_first"}
    for name, v in compared.items():
        assert 2 * v["lower"] < v["limit"] < v["upper"] / 2, name


def test_costs_against_hand_counts():
    """ISSUE 31's table, reckoned again at the published widths, and the
    tiny size by hand."""
    m = program_retention.model_section(published())
    assert C.state_dim(m) == 128 * 129 // 2 == 8256
    # q 26.21 M, k and v 5.24 M each, o 26.21 M, SwiGLU 267.39 M, gate 0.04 M
    assert C.layer_params(m) == (2 * 26_214_400 + 2 * 5_242_880
                                 + 267_386_880 + 40_960)
    assert C.head_params(m) == 777_912_320
    assert C.retention_flops_token_layer(m) == 2 * 8256 * 128 * 48
    assert C.state_bytes_slot_layer(m) == 8 * 8256 * 129 * 4      # 34.1 MB
    # a step at 27 live rows: 5.5 GB of weights + 11.0 GB of state, 20 ms
    least = C.decode_step_min_s(m, 27, PEAK)
    assert least == pytest.approx(
        (5_519_933_440 + 27 * 6 * 2 * 34_080_768) / 819e9)
    assert 0.019 < least < 0.021
    assert C.retention_decode_min_s(m, 1, PEAK) == pytest.approx(83.2e-6,
                                                                 rel=1e-3)
    assert C.retention_chunk_min_s(m, 128, PEAK) == pytest.approx(
        128 * 101_449_728 / 197e12)
    tiny = program_retention.model_section(data("tiny-retention"))
    assert C.state_dim(tiny) == 136
    assert C.layer_params(tiny) == (64 * 64 + 2 * 64 * 32 + 64 * 64 + 64 * 2
                                    + 3 * 64 * 96)
    assert C.retention_flops_token_layer(tiny) == 2 * 136 * 16 * 6
    assert C.state_bytes_slot_layer(tiny) == 2 * 136 * 17 * 4
    assert C.decode_flops(tiny, 3) == 3 * (C.token_flops(tiny)
                                           + 2 * 64 * 256)


def test_a_leaf_is_the_same_array_alone_and_in_the_whole():
    m = program_retention.model_section(data("tiny-retention"))
    whole = W.make_weights(m, 2 ** 31 + 3, np.float32)
    assert set(whole) == set(W.shapes(m))
    get = W.provider(m, 2 ** 31 + 3, np.float32)
    for name in ("model.layers.1.self_attn.q_proj.weight",
                 "model.layers.1.self_attn.g_bias",
                 "model.layers.0.mlp.down_proj.weight",
                 "model.norm.weight", "lm_head.weight"):
        np.testing.assert_array_equal(np.asarray(get(name)),
                                      np.asarray(whole[name]))
    bias = np.asarray(whole["model.layers.1.self_attn.g_bias"])
    assert bias.dtype == np.float32 and 4.0 <= bias.min() <= bias.max() <= 8.0
    other = W.make_weights(m, 4, np.float32)
    assert not np.array_equal(
        np.asarray(other["model.layers.0.self_attn.q_proj.weight"]),
        np.asarray(whole["model.layers.0.self_attn.q_proj.weight"]))


def test_the_weights_names_are_the_models():
    import paddle_tpu as paddle

    config = data("tiny-retention")
    paddle.seed(0)
    model = program_retention.build_model(config, 5)   # raises if they differ
    shapes = W.shapes(program_retention.model_section(config))
    for name, p in model.named_parameters():
        assert tuple(p.shape) == tuple(shapes[name]), name


@pytest.fixture(scope="module")
def rehearsal():
    return run("tiny-retention", "tiny-continue", LIMITS, 2 ** 31 + 9,
               control="fp8+state_dropped+bf16_state")


def test_the_serving_driver_runs_the_cell_and_is_correct(rehearsal):
    r = rehearsal
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 5
    assert set(r["metrics"]) == {"setup_s"}
    assert set(r["compared"]) == {"logit_gap_max", "logit_gap_mean",
                                  "state_s_gap", "state_z_gap",
                                  "state_s_gap_first", "state_z_gap_first",
                                  "wrong_length_requests"}
    assert r["compared"]["state_s_gap"]["value"] < 1e-5
    assert r["notes"]["reference"]["served_again_alike"] == 2
    assert r["compared"]["wrong_length_requests"]["value"] == 0
    assert r["notes"]["compiles_in_window"] == 0
    assert r["notes"]["reference"]["requests"] == 3


def test_the_controls_are_not_correct(rehearsal):
    """The float8 control, the planted fault (the carried state dropped at
    every chunk boundary) and a state kept in bfloat16, through
    ``tools/control.py``'s rule: each fails the limits the program passes.
    The last fails by the state's two numbers alone: the logits cannot see
    it."""
    from benchmark.tools import control

    judged = control.judge(rehearsal["notes"]["reference"], LIMITS)
    assert set(judged) == {"fp8", "state_dropped", "bf16_state"}
    for name in judged:
        assert not judged[name]["correct"], judged
    low = judged["bf16_state"]["compared"]
    assert low["logit_gap_max"]["value"] <= LIMITS["logit_gap_max"]["limit"]
    for name in ("state_s_gap", "state_z_gap", "state_s_gap_first",
                 "state_z_gap_first"):
        assert low[name]["value"] > 1e-3


def test_a_request_that_left_no_state_to_read_is_not_correct():
    """No engine to serve the sample again (or a request that did not end
    in a slot of its own): the state's numbers are missing, and a missing
    number fails."""
    from benchmark import compare, compare_retention, harness

    assert compare_retention.numbers_of(
        [np.zeros(3)], [None])["state_s_gap"] is None
    assert compare_retention.numbers_of([np.zeros(3)], [])[
        "state_z_gap"] is None
    numbers = compare_retention.numbers_of(
        [np.zeros(3)], [np.array([[1e-6, 2e-6], [5e-6, 1e-6]]),
                        np.array([[3e-6, 1e-6], [2e-6, 4e-6]])])
    assert (numbers["state_s_gap"], numbers["state_z_gap"]) == (5e-6, 4e-6)
    assert (numbers["state_s_gap_first"],
            numbers["state_z_gap_first"]) == (3e-6, 2e-6)
    assert not harness.decide(compare.checks_of(
        dict(numbers, state_s_gap=None), LIMITS))
    program_retention.ENGINE_FACTS.pop("engine", None)
    assert program_retention.served_states([{"prompt": [1], "tokens": [2]}]) \
        is None


class _Stamp:
    def __init__(self, prompt_len, seen):
        self.prompt_len, self.seen = prompt_len, seen
        self.first = seen[0][0] if seen else None


def _bag():
    from benchmark import trace_reduce

    m = program_retention.model_section(published())
    snap0 = {"counters": {"serving.state_rows_live_total": 50,
                          "serving.state_layer_steps_total": 6}}
    snap1 = {"counters": {"serving.state_rows_live_total": 50 + 96 * 14,
                          "serving.state_layer_steps_total": 6 + 96}}
    # one segment of 16 steps, 0.25 s; in it 96 decode calls of 2.5 ms; one
    # prefill program with 6 chunk calls of 2 ms
    events = [{"kind": "program", "device": 0, "name": "jit_segment(1)",
               "start": 0.0, "dur": 0.25},
              {"kind": "program", "device": 0, "name": "jit_prefill(2)",
               "start": 0.3, "dur": 0.02}]
    for i in range(96):
        events.append({"kind": "op", "device": 0, "start": i * 2.5e-3,
                       "dur": 2.5e-3, "name": (
                           f"%power_retention_decode.{i} = (f32[32,8,128,5]"
                           "{3,2,1,0}) custom-call(s32[32]{0} %a), "
                           "custom_call_target=\"tpu_custom_call\"")})
    for i in range(6):
        events.append({"kind": "op", "device": 0, "start": 0.3 + i * 3e-3,
                       "dur": 2e-3, "name": (
                           f"%power_retention_chunk.{i} = (bf16[4,8,640,128]"
                           "{3,2,1,0}) custom-call(s32[4]{0} %a), "
                           "custom_call_target=\"tpu_custom_call\"")})
    wait = {"name": "serving.device_wait", "ph": "X", "dur": 9e4}
    return {"kind": "serve", "model": m, "config": published(), "chips": 1,
            "device_kind": "TPU v5 lite", "window": (0.0, 45.0),
            "snap0": snap0, "snap1": snap1,
            "state_bytes_per_slot": 6 * 8 * 65 * 129 * 128 * 4,
            "sink_dropped": 0, "sink_spans": [
                dict(wait, t0=39.0, args={"state_rows_live": 96 * 9,
                                          "state_layer_steps": 96}),
                dict(wait, t0=41.0, args={"state_rows_live": 96 * 14,
                                          "state_layer_steps": 96}),
                {"name": "serving.prefill", "ph": "X", "t0": 42.0, "dur": 2e4,
                 "args": {"state_tokens": 300, "state_padded": 212}},
                {"name": "serving.chunked_prefill", "ph": "X", "t0": 38.0,
                 "dur": 2e4, "args": {"state_tokens": 128,
                                      "state_padded": 0}}],
            "trace_events": trace_reduce.name_ops(events),
            "trace_host_span": (40.0, 45.0),
            "samples": [(41.0, 0.45, 0, 0, 9000, 14, 14)],
            "stamps": [_Stamp(512, [(1.0, 1), (6.0, 321)])]}


def test_the_readers_on_a_hand_made_bag():
    bag = _bag()
    m = bag["model"]
    assert R.state_rows_live(bag) == pytest.approx(14.0)
    assert R.state_bytes_per_slot(bag) == 6 * 8 * 65 * 129 * 128 * 4
    assert R.retention_decode_roofline_pct(bag, DEC) == pytest.approx(
        100 * C.retention_decode_min_s(m, 14, PEAK) / 2.5e-3)
    seg_secs = 96 * 2.5e-3
    assert R.retention_decode_share_pct(bag, DEC) == pytest.approx(100.0)
    assert R.decode_hbm_roofline_pct(bag, 16) == pytest.approx(
        100 * C.decode_step_min_s(m, 14, PEAK) / (seg_secs / 16))
    # six calls of 4 rows x 128 positions (640 / 5 query heads a kv head),
    # and of the one prefill span inside the traced span 300 of 512 real
    assert R.retention_chunk_roofline_pct(bag, CHUNK) == pytest.approx(
        100 * C.retention_chunk_min_s(m, 6 * 512 * 300 / 512, PEAK) / 12e-3)
    assert R.retention_chunk_us_per_tok(bag, CHUNK) == pytest.approx(
        12e3 / (6 * 300))
    want = (C.prefill_flops(m, 512) + 2.0 * C.head_params(m)
            + C.decode_flops(m, 320))
    assert R.serve_mfu_pct(bag) == pytest.approx(
        100 * want / (45.0 * 197e12))
    for share in (R.retention_decode_roofline_pct(bag, DEC),
                  R.retention_chunk_roofline_pct(bag, CHUNK),
                  R.decode_hbm_roofline_pct(bag, 16), R.serve_mfu_pct(bag)):
        assert 0 < share < 100


def test_the_readers_return_none_where_the_program_has_no_such_counter():
    """The parent commit under these benchmark files, or another model's
    bag: no ``serving.state_*`` counter, no kernel of these names."""
    bag = _bag()
    bag["snap0"] = bag["snap1"] = {"counters": {"serving.tokens": 5}}
    for e in bag["sink_spans"]:
        e["args"] = {}
    bag["trace_events"] = [e for e in bag["trace_events"]
                           if e["kind"] == "program"]
    del bag["state_bytes_per_slot"]
    assert R.state_rows_live(bag) is None
    assert R.state_bytes_per_slot(bag) is None
    assert R.retention_decode_roofline_pct(bag, DEC) is None
    assert R.retention_decode_share_pct(bag, DEC) is None
    assert R.retention_chunk_roofline_pct(bag, CHUNK) is None
    assert R.retention_chunk_us_per_tok(bag, CHUNK) is None
    assert R.decode_hbm_roofline_pct(bag, 16) is None
    other = _bag()
    other["model"] = {"hidden_size": 64}               # a dense model's bag
    assert R.serve_mfu_pct(other) is None
    assert R.retention_decode_roofline_pct(other, DEC) is None
    assert R.serve_mfu_pct({"kind": "none"}) is None
