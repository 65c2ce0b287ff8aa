"""The ``nemotron_h`` configuration's harness pieces at a tiny size on the CPU:
the configuration file's facts, the serving driver's control flow, the per-leaf
weights, the costs against hand counts, the readers on hand-made bags, and the
comparison that decides ``correct`` shown to fail under the float8 control,
under the planted fault "what a Mamba-2 layer carries dropped at every chunk
boundary" and under a state kept in bfloat16. Nothing here is a device
metric."""
import functools
import json
import os

import numpy as np
import pytest

from benchmark import costs_nemotron_h as C
from benchmark import program_nemotron_h
from benchmark import weights_nemotron_h as W
from benchmark.readers import nemotron_h as R
from benchmark.tests.test_rehearsal import data, run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "nemotron3nano-think-open"
# this tiny size's own readings on the CPU, float32 program: logits under
# 1e-5; float8 control 0.01-0.2; what is carried dropped 0.01-0.3. The state
# a request leaves against the reference's recurrence: under 1e-5 here; kept
# in bfloat16 2e-3 to 8e-3
LIMITS = {"logit_gap_mean": {"limit": 1e-4}, "logit_gap_p99": {"limit": 1e-3},
          "logit_gap_max": {"limit": None, "not_compared": "printed"},
          **{name: {"limit": 1e-4} for name in (
              "state_s_gap", "state_s_gap_first", "state_conv_gap",
              "state_conv_gap_first")}}
DEC = "^ssd_decode[\\w.\\-]* = .*custom-call\\("
CHUNK = "^ssd_chunk[\\w.\\-]* = .*custom-call\\("
GMM = "^moe_gmm[\\w.\\-]* = .*custom-call\\("
PAGED = "^paged_attention[\\w.\\-]* = .*custom-call\\("
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
FULL_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron3-nano-30b-ep2-d16.json")) as f:
        return json.load(f)


def test_the_configuration_file_holds_the_published_numbers_twice_alike():
    body = published()
    for key, value in body["model"].items():
        if key not in ("_what", "architectures"):
            assert body[key] == value, key
    # the number of routed experts is NOT cut: the router keeps its width
    # of 128; the deployment holds a share of them
    assert body["reduced"] == ["num_hidden_layers", "hybrid_override_pattern"]
    assert body["published"] == {"num_hidden_layers": 52,
                                 "hybrid_override_pattern": FULL_PATTERN}
    assert body["num_hidden_layers"] == 16
    assert body["hybrid_override_pattern"] == FULL_PATTERN[:16] \
        == "MEMEM*EMEMEM*EME"
    # every published width, unchanged
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32,
              "num_key_value_heads": 2, "head_dim": 128,
              "moe_intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "n_routed_experts": 128, "num_experts_per_tok": 6,
              "routed_scaling_factor": 2.5, "vocab_size": 131072,
              "layer_norm_epsilon": 1e-5}
    for key, value in widths.items():
        assert body[key] == value, key
    dep = body["deployment"]
    assert dep["chips_per_layer"] == 2 and dep["experts_held"] == [0, 64]
    assert dep["experts_held_of"] == body["n_routed_experts"] == 128
    assert dep["kind"] == "serve" and dep["dtype"] == "bfloat16"
    assert dep["engine"] == {"max_slots": 32, "max_len": 4096,
                             "page_size": 128, "pool_pages": None,
                             "prompt_buckets": [128], "segment": 16,
                             "do_sample": False}
    assert dep["frontend"] == {"max_queue": 64}
    for key in ("no_rotary", "time_step_limit", "weights", "A_log",
                "dt_bias", "e_score_correction_bias", "state_dtype"):
        assert body["assumed"][key]
    m = program_nemotron_h.model_section(body)
    assert m["experts_held"] == (0, 64)
    assert W.count_params(m) == body["params"] == 5_634_855_744


def test_the_catalogs_keys_are_all_there_as_published():
    """Every number of the catalog's ``config`` under the same key; the two
    cut ones are the only ones that differ."""
    catalog = {"attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
               "expand": 2, "head_dim": 128, "hidden_size": 2688,
               "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
               "mamba_head_dim": 64, "mamba_num_heads": 64,
               "max_position_embeddings": 262144,
               "moe_intermediate_size": 1856,
               "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
               "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
               "norm_eps": 1e-05, "num_attention_heads": 32,
               "num_experts_per_tok": 6, "num_key_value_heads": 2,
               "num_logits_to_keep": 1, "partial_rotary_factor": 1,
               "rope_theta": 10000, "routed_scaling_factor": 2.5,
               "ssm_state_size": 128, "time_step_floor": 0.0001,
               "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
               "vocab_size": 131072}
    body = published()
    for key, value in catalog.items():
        assert body[key] == value, key


def test_the_traffic_file_is_three_quarters_of_its_recorded_knee():
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "think-open.json")) as f:
        t = json.load(f)
    # the knee by tools/sweep.py's own rule over the levelled routers (13 a
    # block: the queue at the end no longer than at the middle; 14: 1 then
    # 4), as the other serving cells took theirs
    assert t["knee_rps"] == 2.6
    assert t["requests_per_block"] == int(
        0.75 * t["knee_rps"] * t["block_s"]) == 9
    assert t["prompt"] == {"dist": "lognormal", "median": 384, "sigma": 0.9,
                           "min": 64, "max": 3072}
    assert t["output"] == {"dist": "lognormal", "median": 640, "sigma": 0.5,
                           "min": 160, "max": 1024}
    assert (t["block_s"], t["ramp_s"], t["pairing_seed"],
            t["check_requests"]) == (5, 20, 33, 6)
    assert t["driver"] == "benchmark.drivers.serve"


def test_the_cells_limits_lie_between_their_two_readings_with_room():
    """Each compared number's limit between the program's largest reading
    and its control's smallest, with room on both sides (``_readings`` /
    ``_state_readings`` say which runs)."""
    with open(os.path.join(ROOT, "benchmark", "limits", CELL + ".json")) as f:
        limits = json.load(f)
    compared = {k: v for k, v in limits.items()
                if not k.startswith("_") and not v.get("not_compared")}
    assert {"logit_gap_mean", "logit_gap_p99", "state_s_gap_first"} \
        <= set(compared)
    for name, v in compared.items():
        assert 1.15 * v["lower"] < v["limit"] < v["upper"] / 1.15, name
    assert "not_compared" in limits["logit_gap_max"]


def test_the_cell_and_its_metrics_are_entered():
    """The cell, its configuration and its ``.think`` metrics are in
    ``BENCHMARK.json``, wherever they stand in their lists."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "think-open"
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["name"] == "nemotron3-nano-30b-ep2-d16"
    assert entry["reduced"] == published()["reduced"]
    assert CELL in next(e for e in m["end_to_end"]
                        if e["name"] == "tpot_mean_ms")["workloads"]
    mine = [p for p in m["per_layer"] if p.get("workloads") == [CELL]]
    names = {p["name"] for p in mine}
    assert all(n.endswith(".think") for n in names) and len(names) == 34
    for n in ("ssd_decode_roofline", "ssd_decode_share", "ssd_chunk_roofline",
              "ssd_chunk_us_per_tok", "moe_gmm_roofline", "moe_gmm_share",
              "moe_experts_hit_per_layer", "moe_load_imbalance",
              "paged_attn_roofline", "state_rows_live",
              "state_bytes_per_slot", "kv_bytes_per_token", "kv_pages_peak",
              "decode_hbm_roofline", "serve_mfu", "decode_step_dev_ms",
              "device_idle", "decode_stall_share"):
        assert n + ".think" in names, n


def test_costs_against_hand_counts():
    """ISSUE 33's sizes, reckoned again at the published widths."""
    m = program_nemotron_h.model_section(published())
    assert (C.layers(m, "M"), C.layers(m, "E"), C.layers(m, "*")) == (7, 7, 2)
    assert C.mamba_inner(m) == 4096 and C.conv_dim(m) == 6144
    # in_proj 2,688 x 10,304 and out_proj 4,096 x 2,688: 38.7 M
    assert C.mamba_params(m) == 2688 * 10304 + 4096 * 2688 == 38_707_200
    assert C.attn_params(m) == 2 * 2688 * 4096 + 2 * 2688 * 256 == 23_396_352
    assert C.expert_params(m) == 2 * 2688 * 1856 == 9_977_856
    # moved as STORED: the first matrix 1,920 columns wide (20.30 MB against
    # the model's 19.96 MB); the operations stay the model's
    assert C.expert_up_stored(m) == 1920
    assert C.expert_bytes(m) == 2 * (2688 * 1920 + 1856 * 2688) == 20_299_776
    assert C.expert_fixed_params(m) == 2688 * 128 + 2 * 2688 * 3712
    assert C.head_params(m) == 2688 * 131072
    assert C.held_share(m) == 0.5
    # on average 3 of a token's 6 routed experts are held here
    assert C.active_params_token(m) == pytest.approx(
        7 * 38_707_200 + 2 * 23_396_352
        + 7 * (C.expert_fixed_params(m) + 3 * 9_977_856))
    assert C.state_bytes_slot_layer(m) == 64 * 64 * 128 * 4    # 2.10 MB
    assert C.conv_bytes_slot_layer(m) == 3 * 6144 * 4
    assert 7 * (C.state_bytes_slot_layer(m) + C.conv_bytes_slot_layer(m)) \
        == 15_196_160
    assert C.ssd_step_flops_token_layer(m) == 4 * 64 * 64 * 128
    assert C.ssd_chunk_flops_token_layer(m) == pytest.approx(
        129 * 128 * 8 + 129 * 64 * 64 + 4 * 64 * 64 * 128)
    # a row's decode call: its state read and written, x / B / C in, y out
    assert C.ssd_decode_min_s(m, 1, PEAK) == pytest.approx(
        (2 * 2_097_152 + 6144 * 2 + 4096 * 4) / 819e9)
    # a step at 19 live rows, 37 experts hit a layer, 20 k live tokens:
    # ISSUE 33 reckons ~7.4 GB, 9.0 ms
    least = C.decode_step_min_s(m, 37, 19, 20_000, 2048, PEAK)
    assert 0.0085 < least < 0.0095
    assert C.moe_gmm_min_s(m, 37, 114, 192, PEAK) == pytest.approx(
        (37 * 20_299_776 + 192 * (2688 + 1920 + 1856 + 2688) * 2) / 819e9)
    assert C.ssd_chunk_min_s(m, 128, PEAK) == pytest.approx(
        128 * C.ssd_chunk_flops_token_layer(m) / 197e12)
    assert C.decode_flops(m, 11, 14) == pytest.approx(
        3 * (2 * (C.active_params_token(m) + C.head_params(m))
             + 7 * C.ssd_step_flops_token_layer(m))
        + C.attn_flops_span(m, 10, 13))


def test_a_leaf_is_the_same_array_alone_and_in_the_whole():
    m = program_nemotron_h.model_section(data("tiny-nemotron-h"))
    whole = W.make_weights(m, 2 ** 31 + 3, np.float32)
    assert set(whole) == set(W.shapes(m))
    with pytest.raises(RuntimeError, match="were not levelled"):
        W.provider(m, 2 ** 31 + 3, np.float32)    # the program comes first
    get = functools.partial(W.leaf, m, 2 ** 31 + 3, dtype=np.float32)
    for name in ("model.layers.0.mixer.in_proj.weight",
                 "model.layers.0.mixer.dt_bias",
                 "model.layers.1.mixer.experts_up",
                 "model.layers.3.mixer.k_proj.weight",
                 "model.norm.weight", "lm_head.weight"):
        np.testing.assert_array_equal(np.asarray(get(name)),
                                      np.asarray(whole[name]))
    a_log = np.asarray(whole["model.layers.2.mixer.A_log"])
    assert a_log.dtype == np.float32
    assert 0.0 <= a_log.min() <= a_log.max() <= np.log(16.0)
    dt = np.log1p(np.exp(np.asarray(whole["model.layers.2.mixer.dt_bias"])))
    assert 1e-3 * 0.999 <= dt.min() <= dt.max() <= 0.1 * 1.001
    assert (np.asarray(whole["model.layers.0.mixer.D"]) == 1).all()
    assert whole["model.layers.1.mixer.experts_up"].shape == (4, 64, 128)
    assert whole["model.layers.1.mixer.e_score_correction_bias"].dtype \
        == np.float32
    other = W.make_weights(m, 4, np.float32)
    assert not np.array_equal(
        np.asarray(other["model.layers.0.mixer.in_proj.weight"]),
        np.asarray(whole["model.layers.0.mixer.in_proj.weight"]))


def test_level_bias_levels_a_skewed_router_and_holds_on_fresh_rows():
    """Scores with a share common to all rows (what seeded weights give a
    router: some experts ahead for every token): under the bias found every
    expert is chosen exactly as often over the rows it was found on, and
    over fresh rows of the same kind to within the draw (768 choices an
    expert: a standard deviation of 3.6 % each on either side of the fit)."""
    import jax

    key = jax.random.PRNGKey(7)
    common = 0.8 * jax.random.normal(key, (128,))

    def scores(k):
        return jax.nn.sigmoid(common + 0.7 * jax.random.normal(
            jax.random.fold_in(key, k), (16384, 128)))

    def loads(s, bias):
        _, ids = jax.lax.top_k(s + bias, 6)
        return np.bincount(np.asarray(ids).ravel(), minlength=128) / 768.0

    before = loads(scores(1), 0.0)
    assert before.max() > 5 and before.min() < 0.2
    bias, found = W.level_bias(scores(1), 6)
    np.testing.assert_allclose(np.asarray(found), 1.0, atol=0.01)
    np.testing.assert_allclose(loads(scores(1), bias), 1.0, atol=0.01)
    fresh = loads(scores(2), bias)
    assert 0.8 < fresh.min() <= fresh.max() < 1.2 and fresh.std() < 0.07
    assert abs(fresh[:64].mean() - 1.0) < 0.015     # the held half's share


def test_the_routers_are_levelled_and_the_reference_gets_the_programs_bias():
    """``build_model`` levels each expert layer's router over the
    configuration's seeded tokens, through the model's own layers; the
    model serves with that bias, ``W.LEVELLED`` keeps it, and the
    reference's provider hands out the same array."""
    import paddle_tpu as paddle
    from paddle_tpu.models.moe_mla import route

    config, seed = data("tiny-nemotron-h"), 2 ** 31 + 11
    m = program_nemotron_h.model_section(config)
    cal = m["router_calibration"]
    assert cal == config["assumed"]["router_calibration"]
    model = program_nemotron_h.build_model(config, seed)
    get = W.provider(m, seed, np.float32)
    ids = W.calibration_tokens(m, seed, cal["sequences"], cal["length"])
    assert ids.shape == (8, 32) and int(ids.max()) < m["vocab_size"]
    level = ids.size * m["num_experts_per_tok"] / m["n_routed_experts"]
    seen = 0
    with paddle.no_grad():
        x = model.model.embed_tokens(paddle.to_tensor(ids))
        for i, layer in enumerate(model.model.layers):
            if layer.kind == "E":
                name = f"model.layers.{i}.mixer.e_score_correction_bias"
                bias = layer.mixer.e_score_correction_bias._value
                assert np.abs(np.asarray(bias)).max() > 0
                np.testing.assert_array_equal(np.asarray(bias),
                                              np.asarray(get(name)))
                chosen, _ = route(
                    layer.norm(x)._value.reshape(-1, m["hidden_size"]),
                    layer.mixer.gate.weight._value, bias,
                    m["num_experts_per_tok"], 2.5)
                load = np.bincount(np.asarray(chosen).ravel(),
                                   minlength=m["n_routed_experts"])
                np.testing.assert_allclose(load / level, 1.0, atol=0.05)
                seen += 1
            x = layer(x)
    assert seen == 2
    # another seed has its own
    assert not any(k[0] == seed + 1 for k in W.LEVELLED)


def test_the_weights_names_are_the_models():
    import paddle_tpu as paddle

    config = data("tiny-nemotron-h")
    paddle.seed(0)
    model = program_nemotron_h.build_model(config, 5)  # raises if they differ
    shapes = W.shapes(program_nemotron_h.model_section(config))
    for name, p in model.named_parameters():
        assert tuple(p.shape) == tuple(shapes[name]), name
    assert model.config.experts_held == (0, 4)


@pytest.fixture(scope="module")
def rehearsal():
    return run("tiny-nemotron-h", "tiny-think", LIMITS, 2 ** 31 + 9,
               control="fp8+state_dropped+bf16_state+bf16")


def test_the_serving_driver_runs_the_cell_and_is_correct(rehearsal):
    r = rehearsal
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 5
    assert set(r["metrics"]) == {"setup_s"}
    assert set(r["compared"]) == {
        "logit_gap_mean", "logit_gap_p99", "state_s_gap",
        "state_s_gap_first", "state_conv_gap", "state_conv_gap_first",
        "wrong_length_requests"}
    assert r["notes"]["reference"]["numbers"]["logit_gap_max"] < 1e-4
    assert r["compared"]["state_s_gap"]["value"] < 1e-5
    assert r["notes"]["reference"]["served_again_alike"] == 2
    assert r["compared"]["wrong_length_requests"]["value"] == 0
    assert r["notes"]["compiles_in_window"] == 0
    assert r["notes"]["reference"]["requests"] == 3


def test_the_controls_are_not_correct(rehearsal):
    """The float8 control, the planted fault (what a Mamba-2 layer carries
    dropped at every chunk boundary) and a state kept in bfloat16, through
    ``tools/control.py``'s rule: each fails the limits the program passes.
    The last fails by the state's numbers alone: the logits cannot see
    it."""
    from benchmark.tools import control

    judged = control.judge(rehearsal["notes"]["reference"], LIMITS)
    assert set(judged) == {"fp8", "state_dropped", "bf16_state", "bf16"}
    for name in ("fp8", "state_dropped", "bf16_state"):
        assert not judged[name]["correct"], judged
    # the witness (the reference at the configuration's own precision, its
    # state float32) is no control: it says what a sound bfloat16 run reads.
    # Here the program is float32, so the witness stands above it
    witness = judged["bf16"]["compared"]
    assert 1e-4 < witness["state_s_gap"]["value"] < 0.1
    heads = rehearsal["notes"]["reference"]["state_gap_by_layer"][
        "first_layer_heads"]
    assert set(heads) == {"program", "norm", "memory_tokens", "fp8",
                          "state_dropped", "bf16_state", "bf16"}
    assert len(heads["program"]) == 2 and len(heads["memory_tokens"]) \
        == len(heads["program"][0]) == len(heads["bf16_state"][1])
    assert max(map(max, heads["program"])) == pytest.approx(
        rehearsal["notes"]["reference"]["state_gap_by_layer"]["program"][
            "s_worst_head"][0], rel=1e-6)
    low = judged["bf16_state"]["compared"]
    assert low["logit_gap_mean"]["value"] <= LIMITS["logit_gap_mean"]["limit"]
    assert low["state_s_gap_first"]["value"] > 1e-3
    # the carried inputs are not the state: rounding the state leaves them
    assert low["state_conv_gap"]["value"] <= 1e-4


def test_a_request_that_left_no_state_to_read_is_not_correct():
    from benchmark import compare, compare_nemotron_h, harness

    assert compare_nemotron_h.numbers_of(
        [np.zeros(3)], [None])["state_s_gap"] is None
    # a row a layer: worst head of S, S as a whole, the carried inputs
    numbers = compare_nemotron_h.numbers_of(
        [np.zeros(3)], [np.array([[1e-6, 7e-7, 2e-6], [9e-6, 5e-6, 1e-6]]),
                        np.array([[3e-6, 8e-7, 1e-6], [2e-6, 1e-6, 4e-6]])])
    assert (numbers["state_s_gap"], numbers["state_conv_gap"]) == (5e-6, 4e-6)
    assert (numbers["state_s_gap_first"],
            numbers["state_conv_gap_first"]) == (3e-6, 2e-6)
    assert not harness.decide(compare.checks_of(
        dict(numbers, state_s_gap=None), LIMITS))
    program_nemotron_h.ENGINE_FACTS.pop("engine", None)
    assert program_nemotron_h.served_states(
        [{"prompt": [1], "tokens": [2]}]) is None


class _Stamp:
    def __init__(self, prompt_len, seen):
        self.prompt_len, self.seen = prompt_len, seen
        self.first = seen[0][0] if seen else None


def _op(name, i, result, start, dur):
    return {"kind": "op", "device": 0, "start": start, "dur": dur, "name": (
        f"%{name}.{i} = {result} custom-call(s32[4]{{0}} %a), "
        "custom_call_target=\"tpu_custom_call\"")}


def _bag():
    from benchmark import trace_reduce

    m = program_nemotron_h.model_section(published())
    counts = {"state_rows_live": 112 * 19, "state_layer_steps": 112,
              "moe_assignments": 112 * 57, "moe_experts_hit": 112 * 37,
              "moe_load_max": 112 * 4, "moe_layer_steps": 112}
    snap0 = {"counters": {f"serving.{k}_total": 5 for k in counts}}
    snap1 = {"counters": {f"serving.{k}_total": 5 + v
                          for k, v in counts.items()}}
    # one segment of 16 steps, 0.28 s: in it 112 ssd_decode calls of 0.25
    # ms, 224 moe_gmm calls of 1 ms, 32 paged_attention calls of 0.05 ms;
    # one prefill program with 7 chunk calls of 1 ms over 4 rows
    events = [{"kind": "program", "device": 0, "name": "jit_segment(1)",
               "start": 0.0, "dur": 0.28},
              {"kind": "program", "device": 0, "name": "jit_prefill(2)",
               "start": 0.3, "dur": 0.02}]
    t = 0.0
    for i in range(112):
        events.append(_op("ssd_decode", i, "(f32[64,4,512]{2,1,0}, "
                          "f32[33,64,64,128]{3,2,1,0})", t, 2.5e-4))
        t += 2.5e-4
    for i in range(224):
        events.append(_op("moe_gmm", i, "bf16[256,1920]{1,0}", t, 1e-3))
        t += 1e-3
    for i in range(32):
        events.append(_op("paged_attention", i, "bf16[32,32,128]{2,1,0}", t,
                          5e-5))
        t += 5e-5
    for i in range(7):
        events.append(_op("ssd_chunk", i, "(f32[4,128,4096]{2,1,0}, "
                          "f32[33,64,64,128]{3,2,1,0})", 0.3 + i * 2e-3,
                          1e-3))
    wait = {"name": "serving.device_wait", "ph": "X", "dur": 9e4}
    return {"kind": "serve", "model": m, "config": published(), "chips": 1,
            "device_kind": "TPU v5 lite", "window": (0.0, 45.0),
            "snap0": snap0, "snap1": snap1,
            "state_bytes_per_slot": 15_196_160, "kv_bytes_per_token": 2048,
            "sink_dropped": 0, "sink_spans": [
                dict(wait, t0=39.0, args={f"{k}": 1 for k in counts}),
                dict(wait, t0=41.0, args=dict(counts)),
                {"name": "serving.prefill", "ph": "X", "t0": 42.0, "dur": 2e4,
                 "args": {"state_tokens": 300, "state_padded": 212}}],
            "trace_events": trace_reduce.name_ops(events),
            "trace_host_span": (40.0, 45.0),
            "samples": [(41.0, 0.6, 200, 1024, 20000, 19, 19)],
            "stamps": [_Stamp(512, [(1.0, 1), (6.0, 321)])]}


def test_the_readers_on_a_hand_made_bag():
    bag = _bag()
    m = bag["model"]
    seg_secs = 112 * 2.5e-4 + 224 * 1e-3 + 32 * 5e-5
    assert R.state_rows_live(bag) == pytest.approx(19.0)
    assert R.state_bytes_per_slot(bag) == 15_196_160
    assert R.kv_bytes_per_token(bag) == 2048
    assert R.experts_hit_per_layer(bag) == pytest.approx(37.0)
    assert R.load_imbalance(bag) == pytest.approx(4 / (57 / 37))
    assert R.ssd_decode_roofline_pct(bag, DEC) == pytest.approx(
        100 * C.ssd_decode_min_s(m, 19, PEAK) / 2.5e-4)
    assert R.kernel_share_pct(bag, DEC) == pytest.approx(
        100 * 112 * 2.5e-4 / seg_secs)
    assert R.kernel_share_pct(bag, GMM) == pytest.approx(
        100 * 224e-3 / seg_secs)
    assert R.moe_gmm_roofline_pct(bag, GMM) == pytest.approx(
        100 * C.moe_gmm_min_s(m, 37, 57, 192, PEAK) / 2e-3)
    assert R.decode_hbm_roofline_pct(bag, 16) == pytest.approx(
        100 * C.decode_step_min_s(m, 37, 19, 20000, 2048, PEAK)
        / (seg_secs / 16))
    assert R.paged_attn_roofline_pct(bag, PAGED) == pytest.approx(
        100 * (20000 * 1024 + 2 * 19 * 32 * 128 * 2) / 819e9 / 5e-5)
    # seven calls of 4 rows x 128 positions, of which 300 of 512 were real
    assert R.ssd_chunk_roofline_pct(bag, CHUNK) == pytest.approx(
        100 * C.ssd_chunk_min_s(m, 7 * 512 * 300 / 512, PEAK) / 7e-3)
    assert R.ssd_chunk_us_per_tok(bag, CHUNK) == pytest.approx(
        7e3 / (7 * 300))
    want = (C.prefill_flops(m, 0, 512) + 2.0 * C.head_params(m)
            + C.decode_flops(m, 513, 833))
    assert R.serve_mfu_pct(bag) == pytest.approx(
        100 * want / (45.0 * 197e12))
    for share in (R.ssd_decode_roofline_pct(bag, DEC),
                  R.ssd_chunk_roofline_pct(bag, CHUNK),
                  R.moe_gmm_roofline_pct(bag, GMM),
                  R.paged_attn_roofline_pct(bag, PAGED),
                  R.decode_hbm_roofline_pct(bag, 16), R.serve_mfu_pct(bag)):
        assert 0 < share < 100


def test_the_readers_return_none_where_the_program_has_no_such_counter():
    """The parent commit under these benchmark files, or another model's
    bag: no counter of these names, no kernel of these names."""
    bag = _bag()
    bag["snap0"] = bag["snap1"] = {"counters": {"serving.tokens": 5}}
    for e in bag["sink_spans"]:
        e["args"] = {}
    bag["trace_events"] = [e for e in bag["trace_events"]
                           if e["kind"] == "program"]
    del bag["state_bytes_per_slot"], bag["kv_bytes_per_token"]
    for value in (R.state_rows_live(bag), R.state_bytes_per_slot(bag),
                  R.kv_bytes_per_token(bag), R.experts_hit_per_layer(bag),
                  R.load_imbalance(bag), R.ssd_decode_roofline_pct(bag, DEC),
                  R.kernel_share_pct(bag, DEC), R.kernel_share_pct(bag, GMM),
                  R.moe_gmm_roofline_pct(bag, GMM),
                  R.paged_attn_roofline_pct(bag, PAGED),
                  R.ssd_chunk_roofline_pct(bag, CHUNK),
                  R.ssd_chunk_us_per_tok(bag, CHUNK),
                  R.decode_hbm_roofline_pct(bag, 16)):
        assert value is None
    other = _bag()
    other["model"] = {"hidden_size": 64}               # a dense model's bag
    for value in (R.serve_mfu_pct(other), R.state_rows_live(other),
                  R.ssd_decode_roofline_pct(other, DEC),
                  R.kernel_share_pct(other, GMM),
                  R.paged_attn_roofline_pct(other, PAGED),
                  R.experts_hit_per_layer(other),
                  R.kv_bytes_per_token(other)):
        assert value is None
    assert R.serve_mfu_pct({"kind": "none"}) is None
