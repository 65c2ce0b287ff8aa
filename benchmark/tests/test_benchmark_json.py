"""BENCHMARK.json and the data files it names, held to the contract's limits
that a file can be checked for."""
import importlib
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"] and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}


# a width is never cut (the contract: hidden, intermediate, latent, state or
# projection sizes, any _dim or _rank, a head size, an expansion factor,
# experts per token; window, scaling factor and rope base likewise)
WIDTH = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|state_size|"
                   r"window|per_tok|factor|theta|expand)")
# counts of what one chip of those that share a layer holds (the
# model-configs guide's section 4): cut only by the deployment's share
SHARE_COUNTS = ("vocab_size", "num_experts", "n_routed_experts",
                "num_attention_heads", "num_key_value_heads")
EXPERT_COUNTS = ("n_routed_experts", "num_experts")


def check_configuration(c, body):
    """One configuration's entry and its file. ``num_attention_heads *
    head_dim`` is the width of q and is not compared with ``hidden_size``
    (Nemotron-H: 2,688 against 32 x 128; Trinity: 3,072 against 48 x 128)."""
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("benchmark/")
    assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
    m, published = body["model"], body["published"]
    chips = body["deployment"].get("chips_per_layer", 1)
    for key in c["reduced"]:
        assert not WIDTH.search(key), key                   # never a width
        assert published[key] != m[key], key
        if key in SHARE_COUNTS:            # one chip's share accounts for it
            assert chips > 1 and m[key] * chips == published[key], key
    if "num_attention_heads" in m:
        assert type(m["head_dim"]) is int and m["head_dim"] > 0
        if "num_key_value_heads" in m:
            assert m["num_attention_heads"] % m["num_key_value_heads"] == 0
    held = body["deployment"].get("experts_held")
    if held is not None:             # [first, end) of the experts held here
        key = next(k for k in EXPERT_COUNTS if k in m)
        assert (held[1] - held[0]) * chips == published.get(key, m[key])


def cells_and_configurations(bench, bodies):
    """``bodies``: each configuration's file, by its path."""
    cfgs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200, (w["name"], len(w["why"]))
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    files = set()
    for c in bench["configs"]:
        assert c["file"] not in files
        files.add(c["file"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        check_configuration(c, bodies[c["file"]])


def read_body(c):
    with open(os.path.join(ROOT, c["file"])) as f:
        return json.load(f)


def test_cells_and_configurations(bench):
    cells_and_configurations(
        bench, {c["file"]: read_body(c) for c in bench["configs"]})


def test_every_cell_has_its_traffic_and_its_limits(bench):
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH_DIR, "limits", w["name"] + ".json"))


def _case(model, published, reduced, **deployment):
    c = {"name": "case", "source": "https://example.org/config.json",
         "file": "benchmark/configs/case.json", "reduced": reduced,
         "why": "a hand-made case of the rules"}
    return c, {"source": c["source"], "reduced": reduced, "model": model,
               "published": published,
               "deployment": dict({"chips_per_layer": 1}, **deployment)}


DENSE = {"hidden_size": 4096, "intermediate_size": 14336,
         "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
         "num_hidden_layers": 16, "vocab_size": 32768, "sliding_window": None}
# nemotron3-nano-30b-ep2-d16's form of a share: the router keeps its
# published 128, the deployment holds 64 of them
HYBRID = {"hidden_size": 2688, "num_attention_heads": 32,
          "num_key_value_heads": 2, "head_dim": 128, "ssm_state_size": 128,
          "moe_intermediate_size": 1856, "n_routed_experts": 128,
          "num_experts_per_tok": 6, "num_hidden_layers": 16,
          "vocab_size": 131072}
# Trinity-Large-Preview on one of 8 chips that share each layer, in the
# guide's form: the held experts and the vocabulary's slice under `reduced`
TRINITY = {"hidden_size": 3072, "intermediate_size": 12288,
           "moe_intermediate_size": 3072, "num_attention_heads": 48,
           "num_key_value_heads": 8, "head_dim": 128, "sliding_window": 4096,
           "num_experts": 32, "num_experts_per_tok": 4,
           "num_hidden_layers": 5, "num_dense_layers": 1,
           "vocab_size": 25024}
TRINITY_PUBLISHED = {"num_hidden_layers": 60, "num_dense_layers": 6,
                     "num_experts": 256, "vocab_size": 200192}
TRINITY_CUT = ["num_hidden_layers", "num_dense_layers", "num_experts",
               "vocab_size"]


def _cut(base, key, value, **deployment):
    return _case(dict(base, **{key: value}), {key: base[key]}, [key],
                 **deployment)


# the refusing line of check_configuration, where a case is refused
WIDE, SHARE, HELD = "WIDTH.search", "m[key] * chips", "held[1] - held[0]"
CASES = {
    "dense": (None, _case(DENSE, {"num_hidden_layers": 32},
                          ["num_hidden_layers"])),
    "hybrid_published_widths": (None, _case(
        HYBRID, {"num_hidden_layers": 52}, ["num_hidden_layers"],
        chips_per_layer=2, experts_held=[0, 64])),
    "trinity_share": (None, _case(TRINITY, TRINITY_PUBLISHED, TRINITY_CUT,
                                  chips_per_layer=8)),
    "trinity_share_experts_held": (None, _case(
        TRINITY, TRINITY_PUBLISHED, TRINITY_CUT, chips_per_layer=8,
        experts_held=[0, 32])),
    "hidden_size_cut": (WIDE, _cut(DENSE, "hidden_size", 2048)),
    "moe_intermediate_size_cut": (WIDE, _cut(
        HYBRID, "moe_intermediate_size", 928, chips_per_layer=2)),
    "head_dim_cut": (WIDE, _cut(DENSE, "head_dim", 64)),
    "sliding_window_cut": (WIDE, _cut(TRINITY, "sliding_window", 1024)),
    "vocab_size_cut_without_a_share": (SHARE, _cut(
        DENSE, "vocab_size", 4096)),
    "share_that_does_not_account": (SHARE, _case(
        dict(TRINITY, vocab_size=50048), TRINITY_PUBLISHED, TRINITY_CUT,
        chips_per_layer=8)),
    "experts_held_that_do_not_account": (HELD, _case(
        HYBRID, {"num_hidden_layers": 52}, ["num_hidden_layers"],
        chips_per_layer=2, experts_held=[0, 32])),
    "query_heads_not_a_multiple_of_kv_heads": (
        "num_attention_heads\"] % m[", _case(
        dict(DENSE, num_key_value_heads=6), {"num_hidden_layers": 32},
        ["num_hidden_layers"])),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_configuration_rules_on_hand_made_cases(name):
    refused_by, (c, body) = CASES[name]
    if refused_by is None:
        check_configuration(c, body)
        return
    with pytest.raises(AssertionError) as refused:
        check_configuration(c, body)
    assert refused_by in str(refused.traceback[-1].statement)


def test_a_trinity_shaped_configuration_and_cell_can_be_entered(bench):
    """BENCHMARK.json as it stands with a configuration of Trinity-Large-
    Preview's published widths on one of 8 chips, and a cell of it,
    appended in memory."""
    c, body = _case(TRINITY, TRINITY_PUBLISHED, TRINITY_CUT,
                    chips_per_layer=8)
    c = dict(c, name="trinity-large-preview-ep8-d5",
             file="benchmark/configs/trinity-large-preview-ep8-d5.json")
    cell = {"name": "trinity-large-chat-open", "config": c["name"],
            "traffic": "chat-open", "chips": 1, "why": "a cell of it"}
    grown = dict(bench, configs=bench["configs"] + [c],
                 workloads=bench["workloads"] + [cell])
    bodies = {x["file"]: read_body(x) for x in bench["configs"]}
    cells_and_configurations(grown, dict(bodies, **{c["file"]: body}))


def test_every_cell_reports_what_its_metrics_move(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for cell in cells:
        mine = [m for m in e2e.values() if reports(m, cell)]
        assert any(m["name"] == "setup_s" for m in mine)
        assert any(m["name"] != "setup_s" for m in mine), cell
        layers = [m for m in bench["per_layer"] if reports(m, cell)]
        assert layers, cell
        assert any("mfu" in m["name"] for m in layers), cell
        for m in layers:
            assert m["moves"] in e2e and reports(e2e[m["moves"]], cell), (cell, m["name"])
    for m in bench["per_layer"]:
        for cell in m.get("workloads", []):
            assert cell in cells


def test_every_per_layer_metric_has_a_reader_of_its_own(bench):
    for m in bench["per_layer"]:
        with open(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        fn = getattr(importlib.import_module(spec["reader"]), spec["function"])
        assert callable(fn) and spec["name"] == m["name"]
        empty = {"kind": "none", "samples": [], "stamps": [], "window": (0.0, 1.0)}
        assert fn(empty, **spec.get("args", {})) is None      # nothing to read: nothing


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel
