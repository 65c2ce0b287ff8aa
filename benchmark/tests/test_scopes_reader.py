"""``readers/scopes.py`` on hand-made ``trace_events`` and a hand-made
program table: the share by scope, by pass and of compiler clones, the 5 %
rule, the agreement rule for two programs of one name, and None wherever
there is nothing whole to read. CPU, no device metric."""
import json
import os

import pytest

from benchmark.readers import scopes as R
from paddle_tpu import profiler
from paddle_tpu.profiler import programs as P

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


class Text:
    """Stands for a compiled program that can only print itself."""

    def __init__(self, module, paths):
        body = "".join(
            f'  %{name} = f32[] fusion(%p), metadata={{op_name="{path}"}}\n'
            if path else f"  %{name} = f32[] copy(%p)\n"
            for name, path in paths.items())
        self.text = f"HloModule {module}\n\nENTRY %main {{\n{body}}}\n"

    def as_text(self):
        return self.text


SEGMENT = {
    "fusion.1": "jit(segment)/while/body/closed_call/attn/dot_general",
    "paged_attention.3": "jit(segment)/while/body/closed_call/attn/"
                         "paged_attention/pallas_call",
    "fusion.2": "jit(segment)/while/body/closed_call/mlp/dot_general",
    "moe_gmm.4": "jit(segment)/while/body/closed_call/moe/moe_experts/"
                 "moe_gmm/pallas_call",
    "iota_reduce_fusion.2": "jit(segment)/while/body/closed_call/lm_head/"
                            "dot_general",
    "fusion.9": "jit(segment)/while/body/closed_call/sample/argmax",
    "fusion.5": "jit(segment)/while/body/closed_call/embed/gather",
    "copy.7": "",
}
STEP = {
    "fusion.1": "jit(one_step)/jvp(attn)/dot_general",
    "fusion.2": "jit(one_step)/transpose(jvp(jvp()))/checkpoint/mlp/"
                "dot_general",
    "fusion.3": "jit(one_step)/transpose(jvp(jvp()))/checkpoint/"
                "rematted_computation/mlp/dot_general",
    "fusion.3.remat": "jit(one_step)/transpose(jvp(jvp()))/checkpoint/"
                      "rematted_computation/mlp/dot_general",
    "fusion.4": "jit(one_step)/optimizer/sub",
    "fusion.6": "jit(one_step)/transpose(jvp(lm_head))/dot_general",
    "copy.8": "",
}


@pytest.fixture
def table(monkeypatch):
    table = profiler.ProgramTable()
    monkeypatch.setattr(P, "_TABLE", table)
    table.note(("segment", 16), Text("jit_segment", SEGMENT))
    table.note("one_step", Text("jit_one_step", STEP))
    return table


def op(program, name, self_s):
    return {"kind": "op", "device": 0, "program": program, "op": name,
            "name": f"{program}:{name}", "self": self_s, "dur": self_s,
            "start": 0.0}


def serve_bag(extra=()):
    secs = {"fusion.1": 1.0, "paged_attention.3": 1.0, "fusion.2": 2.0,
            "moe_gmm.4": 3.0, "iota_reduce_fusion.2": 1.5, "fusion.9": 0.5,
            "fusion.5": 0.25, "copy.7": 0.75}
    events = [op("jit_segment", k, v) for k, v in secs.items()]
    # an async copy that a later operation outlasts: the trace's own
    # arithmetic gives it a negative self time, which counts as nothing
    events += [op("jit_segment", "copy.7", -3.0),
               op("jit_prefill", "fusion.1", 100.0),
               {"kind": "host", "device": -1, "name": "serving.turn",
                "start": 0.0, "dur": 1.0}]
    return {"kind": "serve", "trace_events": events + list(extra),
            "notes": {}}


def spec(name):
    with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
        return json.load(f)


def read(bag, name):
    s = spec(name)
    assert s["reader"] == "benchmark.readers.scopes"
    return getattr(R, s["function"])(bag, **s["args"])


def test_the_decode_shares_by_outermost_scope(table):
    bag = serve_bag()
    got = {m: read(bag, f"decode_{m}_share")
           for m in ("mixer", "ffn", "head", "unscoped")}
    assert got == {"mixer": 20.0, "ffn": 50.0, "head": 20.0,
                   "unscoped": 7.5}                  # of 10 s; embed 2.5
    note = bag["notes"]["device_time_split"]["^jit_segment"]
    assert note["seconds"] == 10.0 and note["unmatched_pct"] == 0.0
    assert note["mixed_fusions_pct"] == 0.0
    assert sum(note["by_scope_pct"].values()) == pytest.approx(100.0)
    assert note["by_scope_pct"]["embed"] == 2.5
    assert note["unscoped_top_pct"] == {"copy.7": 7.5}


def test_the_train_shares_by_pass_scope_and_clone(table):
    secs = {"fusion.1": 2.0, "fusion.2": 3.0, "fusion.3": 1.5,
            "fusion.3.remat": 0.5, "fusion.4": 1.0, "fusion.6": 1.0,
            "copy.8": 1.0}
    bag = {"kind": "train", "notes": {}, "trace_events": [
        op("jit_one_step", k, v) for k, v in secs.items()]}
    want = {"step_fwd_share": 20.0, "step_bwd_share": 40.0,
            "step_recompute_share": 20.0, "step_compiler_clone_share": 5.0,
            "step_optimizer_share": 10.0, "step_attn_share": 20.0,
            "step_mlp_share": 50.0, "step_lm_head_share": 10.0,
            "step_unscoped_share": 10.0}
    assert {m: read(bag, m + ".train") for m in want} == want
    note = bag["notes"]["device_time_split"]["^jit_one_step"]
    assert note["by_pass_pct"]["none"] == 20.0       # optimizer + the copy
    assert sum(note["by_pass_pct"].values()) == pytest.approx(100.0)
    assert note["by_scope_pass_pct"]["mlp/recompute"] == 20.0


def test_five_per_cent_of_unknown_instructions_is_where_a_number_ends(table):
    within = serve_bag([op("jit_segment", "fusion.404", 0.5)])   # 4.76 %
    assert read(within, "decode_head_share") == \
        pytest.approx(100 * 2.0 / 10.5)
    beyond = serve_bag([op("jit_segment", "fusion.404", 0.6)])   # 5.66 %
    assert read(beyond, "decode_head_share") is None
    assert beyond["notes"]["device_time_split"]["^jit_segment"][
        "unmatched_pct"] == pytest.approx(5.66, abs=0.01)


def test_two_programs_of_one_name_count_only_where_they_agree(table):
    other = dict(SEGMENT, **{"fusion.2": SEGMENT["fusion.1"]})   # mlp -> attn
    table.note(("segment", 8), Text("jit_segment", other))
    assert "fusion.2" not in table.ops("jit_segment")
    assert read(serve_bag(), "decode_ffn_share") is None   # 20 % lost
    table.note(("segment", 8), Text("jit_segment", SEGMENT))    # filed anew
    assert read(serve_bag(), "decode_ffn_share") == 50.0


def test_none_where_there_is_nothing_whole_to_read(table, monkeypatch):
    args = spec("decode_head_share")["args"]
    assert R.share_pct({"kind": "none", "samples": [], "stamps": [],
                        "window": (0.0, 1.0)}, **args) is None
    assert R.share_pct({"kind": "serve"}, **args) is None       # untraced
    assert R.share_pct({"kind": "serve", "trace_events": []}, **args) is None
    assert R.share_pct(dict(serve_bag(), kind="other"), **args) is None
    # the traced program was never filed
    assert R.share_pct(serve_bag(), program="^jit_prefill",
                       scope="^attn$") is None
    # a table that lost a program of that name
    small = profiler.ProgramTable(floor=2)
    monkeypatch.setattr(P, "_TABLE", small)
    small.note(("segment", 16), Text("jit_segment", SEGMENT))
    assert R.share_pct(serve_bag(), **args) == 20.0
    small.note("a", Text("jit_a", {}))
    small.note("b", Text("jit_b", {}))
    assert R.share_pct(serve_bag(), **args) is None
    # a program from before the table
    monkeypatch.delattr(profiler, "attribute_device_time")
    assert R.share_pct(serve_bag(), **args) is None


def test_the_thirteen_are_entered_and_only_appended():
    """Four shares of the decode step, each read in the four serving
    cells by one file (one rule for every model: a new model's scopes go
    into the pattern), and nine of the train step."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = bench["per_layer"]
    mine = [m for m in per_layer
            if spec(m["name"])["reader"] == "benchmark.readers.scopes"]
    assert len(mine) == 13 and per_layer[-13:] == mine
    serving = [w["name"] for w in bench["workloads"]
               if w["name"] in next(e for e in bench["end_to_end"] if
                                    e["name"] == "tpot_mean_ms")["workloads"]]
    for m in mine:
        assert (m["unit"], m["better"], m["source"]) == \
            ("%", "lower", "device_trace")
        train = m["name"].endswith(".train")
        assert m["moves"] == ("train_tok_s" if train else "tpot_mean_ms")
        assert m["layer"] == ("train step (jit.TrainStep)" if train
                              else "model step (compiled programs)")
        assert spec(m["name"])["args"]["program"] == (
            "^jit_one_step" if train else "^jit_segment")
        assert m["workloads"] == (["internlm2-d12-pretrain-1chip"] if train
                                  else serving)
    assert sum(m["name"].endswith(".train") for m in mine) == 9
