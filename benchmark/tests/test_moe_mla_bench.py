"""The sparse-expert / latent-attention configuration's harness pieces at a
tiny size on the CPU: the serving driver's control flow, the per-leaf weights,
the costs, the readers on hand-made bags, and the comparison that decides
``correct`` shown to fail under the float8 control and under the planted
fault "one expert's output left out". Nothing here is a device metric."""
import json
import os

import numpy as np
import pytest

from benchmark import costs_moe_mla as C
from benchmark import harness, program_moe_mla
from benchmark import weights_moe_mla as W
from benchmark.readers import moe_mla as R
from benchmark.tests.test_rehearsal import data, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# this tiny size's own readings on the CPU, float32 program: 1e-6 to 2e-5;
# float8 control 0.01-0.1; one expert left out 0.005-0.05
LIMITS = {"logit_gap_max": {"limit": 1e-3}, "logit_gap_mean": {"limit": 1e-4},
          "logit_gap_p99": {"limit": None, "not_compared": "printed only"}}


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash-d5.json")) as f:
        return json.load(f)


def test_the_configuration_file_holds_the_published_numbers_twice_alike():
    body = published()
    for key, value in body["model"].items():
        if key not in ("_what", "architectures"):
            assert body[key] == value, key
    assert body["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert body["published"] == {"num_hidden_layers": 40,
                                 "num_nextn_predict_layers": 1}
    m = program_moe_mla.model_section(body)
    assert m["experts_held"] == (0, 256)
    assert W.count_params(m) == body["params"] == 5558141952
    assert W.count_params(m, routed=False) == \
        body["params_outside_routed_experts"]


def test_costs_at_the_published_widths():
    """ISSUE 27's table, reckoned again: 26.35 M of attention, 4.72 M an
    expert, about 2.7 B active a token over 40 layers."""
    m = program_moe_mla.model_section(published())
    assert C.attn_params(m) == 26_345_472
    assert C.expert_params(m) == 4_718_592 and C.expert_bytes(m) == 9_437_184
    full = dict(m, num_hidden_layers=40)
    assert 2.6e9 < C.active_params_token(full) < 2.8e9
    # a decode step at 19 live rows: 116 of 256 experts a layer, 6.5 ms
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    hit = 256 * (1 - (31 / 32) ** 19)
    least = C.decode_step_min_s(m, hit, 19 * 600, 5760, peak)
    assert 6.0e-3 < least < 7.0e-3
    # both kernels are bound by bytes at decode shapes
    assert C.moe_gmm_min_s(m, hit, 19 * 8, 256, peak) == pytest.approx(
        (hit * 9_437_184 + 256 * 6400 * 2) / 819e9)
    assert C.paged_mla_min_s(m, 12000, 32, peak) > \
        2.0 * 32 * 1088 * 12000 / 197e12


def test_a_leaf_is_the_same_array_alone_and_in_the_whole():
    m = program_moe_mla.model_section(data("tiny-moe-mla"))
    whole = W.make_weights(m, 2 ** 31 + 3, np.float32)
    assert list(whole) == list(W.shapes(m))
    for name in ("model.layers.1.mlp.experts_gate_up",
                 "model.layers.2.mlp.e_score_correction_bias",
                 "model.layers.0.self_attn.kv_b_proj.weight",
                 "model.norm.weight"):
        alone = W.leaf(m, 2 ** 31 + 3, name, np.float32)
        np.testing.assert_array_equal(np.asarray(alone),
                                      np.asarray(whole[name]))
    bias = np.asarray(whole["model.layers.1.mlp.e_score_correction_bias"])
    assert bias.dtype == np.float32 and bias.std() > 0.005
    other = W.leaf(m, 4, "model.layers.1.mlp.experts_gate_up", np.float32)
    assert not np.array_equal(
        np.asarray(other), np.asarray(whole["model.layers.1.mlp.experts_gate_up"]))


def test_the_weights_names_are_the_models():
    import paddle_tpu as paddle

    config = data("tiny-moe-mla")
    paddle.seed(0)
    model = program_moe_mla.build_model(config, 5)     # raises if they differ
    shapes = W.shapes(program_moe_mla.model_section(config))
    for name, p in model.named_parameters():
        assert tuple(p.shape) == tuple(shapes[name]), name


@pytest.fixture(scope="module")
def rehearsal():
    return run("tiny-moe-mla", "tiny-reason", LIMITS, 2 ** 31 + 9,
               control="fp8+expert_left_out")


def test_the_serving_driver_runs_the_cell_and_is_correct(rehearsal):
    r = rehearsal
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 5
    assert set(r["metrics"]) == {"setup_s"}
    assert r["compared"]["wrong_length_requests"]["value"] == 0
    assert r["notes"]["compiles_in_window"] == 0
    assert r["notes"]["reference"]["requests"] == 3


def test_the_controls_are_not_correct(rehearsal):
    """The float8 control and the planted fault (the busiest expert of the
    first sparse layer left out) through ``tools/control.py``'s rule: both
    fail the limit the program passes."""
    from benchmark.tools import control

    detail = rehearsal["notes"]["reference"]
    judged = control.judge(detail, LIMITS)
    assert set(judged) == {"fp8", "expert_left_out"}
    assert not any(c["correct"] for c in judged.values()), judged
    assert 0.0 <= detail["route_flip_share_bf16_hidden"] < 0.5


class _Stamp:
    def __init__(self, prompt_len, seen):
        self.prompt_len, self.seen = prompt_len, seen
        self.first = seen[0][0] if seen else None


def _bag():
    m = program_moe_mla.model_section(published())
    snap0 = {"counters": {"serving.moe_assignments_total": 100,
                          "serving.moe_experts_hit_total": 90,
                          "serving.moe_load_max_total": 20,
                          "serving.moe_layer_steps_total": 4}}
    snap1 = {"counters": {"serving.moe_assignments_total": 100 + 64 * 152,
                          "serving.moe_experts_hit_total": 90 + 64 * 116,
                          "serving.moe_load_max_total": 20 + 64 * 4,
                          "serving.moe_layer_steps_total": 4 + 64}}
    # one program of 16 steps, 0.16 s; in it 128 gmm calls of 1 ms
    events = [{"kind": "program", "device": 0, "name": "jit_segment_unfused(1)",
               "start": 0.0, "dur": 0.16}]
    for i in range(128):
        events.append({"kind": "op", "device": 0, "start": i * 1.2e-3,
                       "dur": 1e-3, "name": (
                           f"%moe_gmm.{i} = bf16[256,1536]{{1,0}} custom-call("
                           "s32[258]{0} %a), "
                           "custom_call_target=\"tpu_custom_call\"")})
    for i in range(80):
        events.append({"kind": "op", "device": 0, "start": 0.1536 + i * 6e-5,
                       "dur": 5e-5, "name": (
                           f"%paged_mla_attention.{i} = bf16[32,32,512]"
                           "{2,1,0} custom-call(s32[1024]{0} %a), "
                           "custom_call_target=\"tpu_custom_call\"")})
    from benchmark import trace_reduce

    return {"kind": "serve", "model": m, "config": published(), "chips": 1,
            "device_kind": "TPU v5 lite", "window": (0.0, 45.0),
            "snap0": snap0, "snap1": snap1, "kv_bytes_per_token": 5760,
            # two segments fetched inside the traced span, one before it
            "sink_dropped": 0, "sink_spans": [
                {"name": "serving.device_wait", "ph": "X", "t0": t0,
                 "dur": 9e4, "args": {
                     "moe_assignments": 32 * 152, "moe_experts_hit": 32 * hit,
                     "moe_load_max": 32 * 4, "moe_layer_steps": 32}}
                for t0, hit in ((39.0, 90), (41.0, 116), (43.0, 116))],
            "trace_events": trace_reduce.name_ops(events),
            "trace_host_span": (40.0, 45.0),
            "samples": [(41.0, 0.6, 100, 1024, 12000, 19, 19)],
            "stamps": [_Stamp(256, [(1.0, 1), (6.0, 513)])]}


def test_the_readers_on_a_hand_made_bag():
    bag = _bag()
    assert R.experts_hit_per_layer(bag) == pytest.approx(116.0)
    assert R.load_imbalance(bag) == pytest.approx(4 / (152 / 116))
    assert R.kv_bytes_per_token(bag) == 5760
    gmm = "^moe_gmm[\\w.\\-]* = .*custom-call\\("
    mla = "^paged_mla_attention[\\w.\\-]* = .*custom-call\\("
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    least = C.moe_gmm_min_s(bag["model"], 116, 152, 256, peak)
    assert R.moe_gmm_roofline_pct(bag, gmm) == pytest.approx(
        100 * least / 2e-3)                   # a pair of 1 ms calls
    seg_secs = 128 * 1e-3 + 80 * 5e-5
    assert R.moe_gmm_share_pct(bag, gmm) == pytest.approx(
        100 * 0.128 / seg_secs)
    assert R.paged_mla_roofline_pct(bag, mla) == pytest.approx(
        100 * C.paged_mla_min_s(bag["model"], 12000, 32, peak) / 5e-5)
    step_s = seg_secs / 16
    assert R.decode_hbm_roofline_pct(bag, 16) == pytest.approx(
        100 * C.decode_step_min_s(bag["model"], 116, 12000, 5760, peak)
        / step_s)
    want = (C.prefill_flops(bag["model"], 0, 256)
            + 2.0 * C.head_params(bag["model"])
            + C.decode_flops(bag["model"], 257, 256 + 513))
    assert R.serve_mfu_pct(bag) == pytest.approx(
        100 * want / (45.0 * 197e12))
    for share in (R.moe_gmm_roofline_pct(bag, gmm),
                  R.paged_mla_roofline_pct(bag, mla),
                  R.decode_hbm_roofline_pct(bag, 16), R.serve_mfu_pct(bag)):
        assert 0 < share


def test_the_readers_return_none_where_the_program_has_no_such_counter():
    """The parent commit under these benchmark files: no ``serving.moe_*``
    counter, no kernel of these names, a dense model in the bag."""
    bag = _bag()
    bag["snap0"] = bag["snap1"] = {"counters": {"serving.tokens": 5}}
    for e in bag["sink_spans"]:
        e["args"] = {}
    bag["trace_events"] = [e for e in bag["trace_events"]
                           if e["kind"] == "program"]
    gmm = "^moe_gmm[\\w.\\-]* = .*custom-call\\("
    assert R.experts_hit_per_layer(bag) is None
    assert R.load_imbalance(bag) is None
    assert R.moe_gmm_roofline_pct(bag, gmm) is None
    assert R.moe_gmm_share_pct(bag, gmm) is None
    assert R.paged_mla_roofline_pct(bag, gmm) is None
    assert R.decode_hbm_roofline_pct(bag, 16) is None
    bag["model"] = {"hidden_size": 64}
    del bag["kv_bytes_per_token"]
    assert R.serve_mfu_pct(bag) is None and R.kv_bytes_per_token(bag) is None
