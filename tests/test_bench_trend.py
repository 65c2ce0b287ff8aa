"""Bench-trend harness CI smoke (ISSUE 10 satellite).

Guards two things:

* **schema drift** — every checked-in ``BENCH_*.json`` round must parse
  (the real series already exhibits the drift: r02-r04 carry a
  ``parsed`` dict, r05/r06 only a truncated stdout ``tail``, the key
  set changed every round, r06 is a CPU smoke) — a driver format change
  that breaks the series check must fail HERE, not silently in some
  future round;
* **regression detection** — the harness reports the known
  ``decode_tok_s_vs_floor`` 0.81x regression at r05 from the
  checked-in data, and exits nonzero on an injected regression fixture.

The harness is loaded BY FILE PATH (like the repo-root
``tools/bench_trend.py`` wrapper does) so this smoke also proves the
no-framework-import contract CI relies on.
"""
import importlib.util
import json
import pathlib
import shutil
import subprocess
import sys

import pytest

_ROOT = pathlib.Path(__file__).resolve().parents[1]
_IMPL = _ROOT / "paddle_tpu" / "tools" / "bench_trend.py"


@pytest.fixture(scope="module")
def bt():
    spec = importlib.util.spec_from_file_location("_bt_test", _IMPL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_checked_in_round_parses(bt):
    """Schema-drift guard over the real series: no parse errors, and
    the drifted sources are recovered the way they actually drifted."""
    data = bt.collect(str(_ROOT))
    assert data["baseline"] is not None
    rounds = {r["name"]: r for r in data["rounds"]}
    assert len(rounds) >= 4  # r01, r04, r05, r06
    errors = {n: r["error"] for n, r in rounds.items() if r["error"]}
    assert not errors, f"unparseable bench rounds: {errors}"
    # r01 recorded nothing (empty tail) — data-free, not broken
    assert rounds["BENCH_r01"]["metrics"] is None
    # r04: the parsed dict; r05/r06: recovered from the tail
    assert rounds["BENCH_r04"]["source"] == "parsed"
    assert rounds["BENCH_r05"]["source"] == "tail-braced"
    assert rounds["BENCH_r06"]["source"] == "tail"
    # the key-set drift is real data, not an artifact: spot-check known
    # values across the drifted schemas
    assert rounds["BENCH_r04"]["metrics"][
        "decode_vs_streaming_floor"] == 3.04
    assert rounds["BENCH_r05"]["metrics"][
        "decode_vs_streaming_floor"] == 1.42
    assert rounds["BENCH_r05"]["metrics"][
        "e2e.decode_tok_s_vs_floor"] == pytest.approx(0.806)
    assert rounds["BENCH_r06"]["platform"] == "cpu"


def test_reports_known_decode_floor_regression(bt):
    report = bt.analyze(str(_ROOT))
    assert not report["parse_errors"]
    # the CPU smoke round is excluded from TPU-absolute comparisons
    assert any(e["round"] == "BENCH_r06"
               for e in report["incomparable"])
    known = [e for e in report["regressions"]
             if e["metric"] == "decode_tok_s_vs_floor"
             and e["kind"] == "calibrated"]
    assert known, ("the known decode_tok_s_vs_floor 0.81x regression at "
                   "r05 was not reported")
    assert known[0]["round"] == "BENCH_r05"
    assert known[0]["ratio"] == pytest.approx(0.806)
    # and it renders in the markdown report
    md = bt.render_markdown(report)
    assert "decode_tok_s_vs_floor" in md and "0.806" in md


def _fixture_root(tmp_path, extra_round=None):
    root = tmp_path / "bench"
    root.mkdir(parents=True)
    for name in ("BENCH_BASELINE.json", "BENCH_r04.json",
                 "BENCH_r05.json"):
        shutil.copy(_ROOT / name, root / name)
    if extra_round is not None:
        (root / "BENCH_r07.json").write_text(json.dumps(extra_round))
    return root


def test_injected_regression_fixture_exits_nonzero(bt, tmp_path):
    """A fabricated round whose calibrated ratios collapse must drive a
    nonzero exit (the CI contract), and a clean fixture must exit 0."""
    bad = {"n": 7, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": {"platform": "tpu", "device": "TPU v5 lite",
                      "tokens_per_sec": 50000.0,
                      "e2e_vs_baseline": {"llama_train_tok_s_per_tflop":
                                          0.4}}}
    rc = bt.main(["--root", str(_fixture_root(tmp_path, bad)), "-q"])
    assert rc == 1
    # clean fixture: no r05 (the known regression) -> exit 0
    clean_root = tmp_path / "clean"
    clean_root.mkdir()
    shutil.copy(_ROOT / "BENCH_BASELINE.json",
                clean_root / "BENCH_BASELINE.json")
    good = {"n": 7, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"platform": "tpu", "device": "TPU v5 lite",
                       "decode_vs_streaming_floor": 1.4,
                       "e2e_vs_baseline": {"decode_tok_s_vs_floor":
                                           1.01}}}
    (clean_root / "BENCH_r07.json").write_text(json.dumps(good))
    assert bt.main(["--root", str(clean_root), "-q"]) == 0


def test_gate_violation_detected(bt, tmp_path):
    over = {"n": 7, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"platform": "cpu", "device": "cpu",
                       "perfwatch_overhead_pct": 7.5}}
    report = bt.analyze(str(_fixture_root(tmp_path, over)))
    hits = [e for e in report["gate_violations"]
            if e["metric"] == "perfwatch_overhead_pct"]
    assert hits and hits[0]["value"] == 7.5 and hits[0]["limit"] == 3.0
    assert bt.main(["--root",
                    str(tmp_path / "bench"), "-q"]) == 1


def test_tp_gates_cover_e8_and_tolerate_old_rounds(bt, tmp_path):
    """The e8 TP-serving gates (dispatch overhead, member-death
    recovery, lost requests, stream divergence) are declared in GATES,
    fire on an over-limit round, and — critically — the checked-in
    OLDER rounds that predate the section stay clean (absent metrics
    are skipped, not treated as violations)."""
    for gate in ("tp_dispatch_overhead_pct", "tp_member_death_recovery_s",
                 "tp_lost_requests", "tp_stream_divergence"):
        assert gate in bt.GATES, f"e8 gate {gate} missing from GATES"
    # rounds r04/r05 predate e8 entirely: no tp_* keys, no violations
    report = bt.analyze(str(_fixture_root(tmp_path)))
    assert not any(e["metric"].startswith("tp_")
                   for e in report["gate_violations"])
    # a round carrying the new section: in-gate numbers stay clean...
    ok = {"n": 8, "cmd": "python bench.py", "rc": 0, "tail": "",
          "parsed": {"platform": "cpu", "device": "cpu",
                     "tp_degree": 2, "tp_dispatch_overhead_pct": 1.2,
                     "tp_member_death_recovery_s": 4.5,
                     "tp_lost_requests": 0, "tp_stream_divergence": 0}}
    report = bt.analyze(str(_fixture_root(tmp_path / "ok", ok)))
    assert not any(e["metric"].startswith("tp_")
                   for e in report["gate_violations"])
    # ...and an over-limit round trips every tp gate it violates
    bad = {"n": 8, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": {"platform": "cpu", "device": "cpu",
                      "tp_dispatch_overhead_pct": 35.0,
                      "tp_member_death_recovery_s": 120.0,
                      "tp_lost_requests": 2,
                      "tp_stream_divergence": 1}}
    report = bt.analyze(str(_fixture_root(tmp_path / "bad", bad)))
    tripped = {e["metric"] for e in report["gate_violations"]
               if e["metric"].startswith("tp_")}
    assert tripped == {"tp_dispatch_overhead_pct",
                       "tp_member_death_recovery_s", "tp_lost_requests",
                       "tp_stream_divergence"}
    assert bt.main(["--root", str(tmp_path / "bad" / "bench"),
                    "-q"]) == 1


def test_megakernel_gates_cover_e11_and_rearm_decode_floor(bt, tmp_path):
    """The e11 decode-megakernel gates: speedup must clear 1x, the
    fused device_wait p50 ratio must stay near parity, and the decode
    floor is RE-ARMED at >= 1.0 — but only for rounds that carry the
    e11 section (the conditional 3-tuple gate form), so the checked-in
    pre-megakernel rounds (r05 stands at 0.81x) stay clean."""
    assert bt.GATES["decode_megakernel_speedup"] == ("min", 1.0)
    assert bt.GATES["decode_vs_streaming_floor"] == (
        "min", 1.0, "decode_megakernel_speedup")
    # pre-e11 rounds below the floor: the conditional gate stays silent
    old = {"n": 7, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": {"platform": "cpu", "device": "cpu",
                      "decode_vs_streaming_floor": 0.81}}
    report = bt.analyze(str(_fixture_root(tmp_path / "old", old)))
    assert not any(e["metric"] == "decode_vs_streaming_floor"
                   for e in report["gate_violations"])
    # an e11 round that failed to re-win the floor trips all three
    bad = {"n": 8, "cmd": "python bench.py", "rc": 0, "tail": "",
           "parsed": {"platform": "cpu", "device": "cpu",
                      "decode_megakernel_speedup": 0.8,
                      "megakernel_device_wait_ratio": 2.0,
                      "decode_vs_streaming_floor": 0.81}}
    report = bt.analyze(str(_fixture_root(tmp_path / "bad", bad)))
    tripped = {e["metric"] for e in report["gate_violations"]
               if e["round"] == "BENCH_r07"}
    assert tripped == {"decode_megakernel_speedup",
                       "megakernel_device_wait_ratio",
                       "decode_vs_streaming_floor"}
    assert bt.main(["--root", str(tmp_path / "bad" / "bench"),
                    "-q"]) == 1
    # an e11 round that re-won the floor passes every megakernel gate
    ok = {"n": 8, "cmd": "python bench.py", "rc": 0, "tail": "",
          "parsed": {"platform": "cpu", "device": "cpu",
                     "decode_megakernel_speedup": 1.3,
                     "megakernel_device_wait_ratio": 0.92,
                     "decode_vs_streaming_floor": 1.05}}
    report = bt.analyze(str(_fixture_root(tmp_path / "ok", ok)))
    assert not any(e["round"] == "BENCH_r07"
                   for e in report["gate_violations"])


def test_unreadable_round_is_a_parse_error(bt, tmp_path):
    root = _fixture_root(tmp_path)
    (root / "BENCH_r08.json").write_text("{not json")
    report = bt.analyze(str(root))
    assert any(e["round"] == "BENCH_r08" for e in report["parse_errors"])
    assert bt.main(["--root", str(root), "-q"]) == 2


def test_repo_root_wrapper_runs_without_framework_import(tmp_path):
    """``python tools/bench_trend.py`` must work with no jax / framework
    import (CI runs it before any heavy setup) — prove it by running the
    wrapper with imports of paddle_tpu poisoned."""
    out = tmp_path / "report.json"
    proc = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; "
         # poison the heavy imports: any `import jax`/`import paddle_tpu`
         # inside the harness would raise instead of silently working
         "sys.modules['jax'] = None; sys.modules['paddle_tpu'] = None; "
         "out, wrapper = sys.argv[1], sys.argv[2]; "
         "sys.argv = ['bench_trend', '-q', '--json', out]; "
         "runpy.run_path(wrapper, run_name='__main__')",
         str(out), str(_ROOT / "tools" / "bench_trend.py")],
        capture_output=True, text=True, cwd=str(_ROOT), timeout=60)
    # exit 1: the checked-in series contains the known regression
    assert proc.returncode == 1, proc.stderr
    report = json.loads(out.read_text())
    assert any(e["metric"] == "decode_tok_s_vs_floor"
               for e in report["regressions"])


def test_diff_rounds_backend(bt):
    rows = bt.diff_rounds(str(_ROOT / "BENCH_r04.json"),
                          str(_ROOT / "BENCH_r05.json"))
    d = {m: ratio for m, _, _, ratio in rows}
    assert d["decode_vs_streaming_floor"] == pytest.approx(1.42 / 3.04)
