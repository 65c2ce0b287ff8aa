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


def test_cells_and_configurations(bench):
    cfgs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200, (w["name"], len(w["why"]))
        assert NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH_DIR, "limits", w["name"] + ".json"))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and c["file"] not in files
        files.add(c["file"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|_size|expert|factor)", key)   # never a width
            assert body["published"][key] != body["model"][key]
        m = body["model"]
        assert m["hidden_size"] == m["num_attention_heads"] * m["head_dim"]


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
