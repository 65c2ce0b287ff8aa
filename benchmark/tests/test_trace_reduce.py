import os

import pytest

from benchmark import trace_reduce as T

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    """Two short traces recorded on a TPU v5 lite (my chip run, PR 24): the
    engine at Mistral widths, 2 layers, 8 slots ("serve"), and three train
    steps at InternLM2 widths, 2 layers ("train")."""
    events = T.load_events(os.path.join(HERE, "data", "recorded_trace.json.gz"))
    return {tag: [e for e in events if e["trace"] == tag]
            for tag in ("serve", "train")}


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.length(T.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert T.op_name("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p)") == "fusion.12"
    assert T.program_name("jit_segment_unfused(12670261937519333165)") == "jit_segment_unfused"
    assert T.hlo_text("%a.1 = bf16[4,8]{1,0:T(8,128)(2,1)} custom-call(s32[8]{0} %x), "
                      'custom_call_target="tpu_custom_call"') == \
        'a.1 = bf16[4,8] custom-call(s32[8]), custom_call_target="tpu_custom_call"'


def test_serving_trace(recorded):
    ev = recorded["serve"]
    s = T.summary(ev, 1)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(0.09616319, rel=1e-4)
    programs = {e["program"] for e in ev if e["kind"] == "program"}
    assert {"jit_segment_unfused", "jit_prefill", "jit_chunk_step", "jit_final_chunk"} <= programs
    runs, secs = T.program_seconds(ev, "^jit_segment")
    assert runs == 3 and secs == pytest.approx(0.0819, rel=1e-2)
    # 2 layers x 16 steps x 3 segments of paged attention, a Mosaic kernel
    calls, ksecs = T.op_seconds(ev, r"custom-call\(s32\[.*tpu_custom_call", "^jit_segment")
    assert calls == 96 and 0 < ksecs < secs
    # every operation is named <jitted program>:<HLO op>; a loop's time is its body's
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert all(":" in n and not n.startswith("?") for n in names)
    assert not any(":while" in n for n in names)
    assert len(s["breakdown"]["device_ops"]) <= 10 and len(s["breakdown"]["idle_gaps"]) <= 10
    assert {g[0] for g in s["breakdown"]["idle_gaps"]} & {"serving.prefill", "serving.chunked_prefill"}


def test_training_trace(recorded):
    ev = recorded["train"]
    runs, secs = T.program_seconds(ev, "^jit_one_step")
    assert runs == 3 and secs / runs == pytest.approx(0.1899, rel=1e-2)
    fwd = T.op_seconds(ev, r"= \(bf16\[[\d,]+\], f32\[[\d,]+,1\]\) custom-call\(.*tpu_custom_call")
    assert fwd[0] == 12        # 2 layers x (forward + recomputation) x 3 steps
    bwd = T.op_seconds(ev, r"custom-call\(bf16\[[\d,]+\], bf16\[[\d,]+\], bf16\[[\d,]+\], bf16\[[\d,]+\], "
                           r"f32\[[\d,]+,1\], f32\[[\d,]+,1\]\).*tpu_custom_call")
    assert bwd[0] == 12        # dq and dk/dv kernels x 2 layers x 3 steps
    assert T.busy_seconds(ev) / T.summary(ev, 1)["window_s"] > 0.99
