"""tpu-lint: the analyzer's own test suite + the tier-1 repo gate.

Three layers (ISSUE 13):

* **Fixture corpus** — minimal bad/good snippets per rule under
  ``tests/fixtures/tpu_lint/`` (a deliberate lock-order cycle, a fake
  jit entry, every hygiene violation). Each rule must fire exactly
  where the fixture says, and the clean mirror must produce nothing.
* **Repo gate** — ``analyze paddle_tpu/`` is clean modulo the
  checked-in baseline (``TPU_LINT_BASELINE.json``, reasons required),
  and seeding any bad fixture INTO a package tree makes the same gate
  fail with the expected rule id — proof the gate would catch the edit.
* **Lock-graph reality** — the lock-discipline pass encodes the actual
  fleet lock graph: the ``--json`` report names the real locks in
  ``distributed/rpc.py`` / ``core/telemetry.py`` / the router tier
  (``models/journal.py`` WAL, ``models/remote.py`` replica server —
  the router pump itself is single-threaded by design and owns no
  lock), and an ordering inversion injected into a fixture copy is
  reported as a cycle.

Pure AST: the engine is loaded standalone from its file — no JAX
import — so this whole file runs without a backend.
"""
import json
import pathlib
import shutil

import pytest

from _tpu_lint_loader import lint_engine as _lint

_REPO = pathlib.Path(__file__).resolve().parents[1]
_PKG = _REPO / "paddle_tpu"
_FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures" / "tpu_lint"


@pytest.fixture(scope="module")
def fixture_findings():
    return _lint().run([_FIXTURES])


def _rules_at(findings, filename):
    return {(f.rule, f.line) for f in findings if f.path == filename}


def _rules_of(findings, filename):
    return {f.rule for f in findings if f.path == filename}


# ------------------------------------------------------ fixture corpus


def test_tracer_rules_fire_on_fixture(fixture_findings):
    got = _rules_at(fixture_findings, "bad_tracer.py")
    expected = {
        ("tracer-wall-clock", 12),      # time.time() in entry
        ("tracer-py-rng", 13),          # random.random()
        ("tracer-py-rng", 14),          # np.random.uniform()
        ("tracer-concretize", 15),      # .item()
        ("tracer-concretize", 16),      # float(y)
        ("tracer-np-host", 17),         # np.asarray(x)
        ("tracer-host-branch", 18),     # if x > 0
        ("tracer-host-branch", 20),     # while y < t
        ("tracer-wall-clock", 26),      # helper(), via the call graph
    }
    missing = expected - got
    assert not missing, f"tracer rules did not fire: {sorted(missing)}"


def test_tracer_reachability_covers_helpers(fixture_findings):
    """helper() is never wrapped itself — it is traced only because the
    jit entry calls it. The finding at its line proves the call graph,
    not just the entry scan."""
    assert ("tracer-wall-clock", 26) in _rules_at(
        fixture_findings, "bad_tracer.py")


def test_tracer_structural_checks_exempt(fixture_findings):
    """`is None` / isinstance() on traced args resolve at trace time —
    ok_entry must contribute no findings."""
    bad = [f for f in fixture_findings
           if f.path == "bad_tracer.py" and f.line >= 33]
    assert not bad, f"structural trace-time checks flagged: {bad}"


def test_recompile_rules_fire_on_fixture(fixture_findings):
    got = _rules_at(fixture_findings, "bad_recompile.py")
    expected = {
        ("pytree-dict-order", 14),            # for k in d (For loop)
        ("pytree-dict-order", 21),            # comprehension
        ("recompile-churn", 31),              # f-string arg
        ("recompile-churn", 32),              # len(...) arg
        ("recompile-unhashable-static", 33),  # list literal, static pos
        ("recompile-unhashable-static", 34),  # dict literal, static kw
    }
    missing = expected - got
    assert not missing, f"recompile rules did not fire: {sorted(missing)}"
    # the stable literal at the last call site is ONE cache entry: ok
    assert not any(line >= 35 for _, line in got)


def test_lock_rules_fire_on_fixture(fixture_findings):
    got = _rules_at(fixture_findings, "bad_locks.py")
    assert ("lock-blocking-call", 34) in got      # time.sleep under lock
    assert ("lock-blocking-call", 35) in got      # .join under lock
    assert ("lock-blocking-call", 36) in got      # subprocess.run
    assert ("lock-mixed-mutation", 51) in got     # unlocked append
    assert ("lock-mixed-mutation", 52) in got     # unlocked count += 1
    cycle = [f for f in fixture_findings
             if f.path == "bad_locks.py" and f.rule == "lock-order-cycle"]
    # the a/b inversion and the non-reentrant self-deadlock
    assert len(cycle) >= 2
    inversion = [f for f in cycle if "lock_a" in f.why and "lock_b" in f.why]
    assert inversion, "a->b vs b->a inversion not named in the finding"


def test_locked_helper_inference(fixture_findings):
    """_helper_under_lock mutates _items with no `with` of its own, but
    its only call site holds the lock — the inference must NOT flag it."""
    assert not any(
        f.path == "bad_locks.py" and f.rule == "lock-mixed-mutation"
        and 55 <= f.line <= 58
        for f in fixture_findings)


def test_hygiene_rules_fire_on_fixture(fixture_findings):
    assert _rules_of(fixture_findings, "bad_except.py") >= {
        "bare-except-pass", "wall-clock"}
    # the `# wall-clock` sanctioned line must be pragma-suppressed
    assert not any(f.path == "bad_except.py" and f.line == 26
                   for f in fixture_findings)
    assert _rules_of(fixture_findings, "bad_alias.py") == {
        "wall-clock-alias"}


def test_partial_wrapped_pallas_kernels_are_swept(fixture_findings):
    """Pallas kernels reach pallas_call through functools.partial (the
    conventional way to close static params over the kernel) — both the
    direct-argument form and the local-binding form must register the
    kernel body as a jit entry and sweep it with the tracer rules."""
    got = _rules_at(fixture_findings, "bad_partial_kernel.py")
    expected = {
        ("tracer-wall-clock", 15),    # _direct_kernel: time.time()
        ("tracer-host-branch", 16),   # _direct_kernel: if x_ref[0] > t
        ("tracer-concretize", 23),    # _bound_kernel: .item()
    }
    missing = expected - got
    assert not missing, (
        f"partial-wrapped kernels not swept: {sorted(missing)}")


def test_partial_bound_params_are_static(fixture_findings):
    """Params bound BY the partial are baked Python values — branching
    on them is trace-time config, not a tracer leak."""
    static_branches = [
        f for f in fixture_findings
        if f.path == "bad_partial_kernel.py" and f.line in (13, 21)]
    assert not static_branches, (
        f"partial-bound static params flagged: {static_branches}")


def test_good_fixture_is_clean(fixture_findings):
    noise = [f for f in fixture_findings if f.path == "good_clean.py"]
    assert not noise, f"clean fixture produced findings: {noise}"


def test_pragma_suppresses_next_line(tmp_path):
    src = ("import time\n"
           "# tpu-lint: disable=wall-clock\n"
           "T0 = time.time()\n"
           "T1 = time.time()  # tpu-lint: disable=wall-clock\n"
           "T2 = time.time()\n")
    f = tmp_path / "prag.py"
    f.write_text(src)
    found = _lint().run([f], rules={"wall-clock"})
    assert [x.line for x in found] == [5]


# ------------------------------------------------------------ repo gate


def test_repo_is_lint_clean():
    """THE gate: the shipped tree passes its own analyzer (modulo the
    checked-in baseline — whose every entry must carry a reason)."""
    eng = _lint()
    findings = eng.run([_PKG])
    entries = eng.load_baseline(_REPO / "TPU_LINT_BASELINE.json")
    findings, _ = eng.apply_baseline(findings, entries)
    assert not findings, (
        "tpu-lint found new violations (fix them, or pragma with a "
        "justification — see README 'Static analysis'):\n  "
        + "\n  ".join(map(repr, findings)))


@pytest.mark.parametrize("fixture,expected_rule", [
    ("bad_tracer.py", "tracer-wall-clock"),
    ("bad_recompile.py", "recompile-churn"),
    ("bad_locks.py", "lock-order-cycle"),
    ("bad_except.py", "bare-except-pass"),
    ("bad_alias.py", "wall-clock-alias"),
    ("bad_partial_kernel.py", "tracer-concretize"),
])
def test_seeded_bad_snippet_fails_the_gate(tmp_path, fixture,
                                           expected_rule):
    """Copy a package subtree shape, seed one bad fixture into it, and
    the same gate run must fail with the expected rule id — the proof
    that a tracer-unsafe/deadlocky edit cannot land silently."""
    pkg = tmp_path / "paddle_tpu" / "models"
    pkg.mkdir(parents=True)
    shutil.copy(_FIXTURES / fixture, pkg / "seeded.py")
    findings = _lint().run([tmp_path / "paddle_tpu"])
    assert any(f.rule == expected_rule for f in findings), (
        f"seeding {fixture} into paddle_tpu/models/ did not trip "
        f"{expected_rule}; got {findings}")


def test_analyzer_is_self_clean():
    """analyze paddle_tpu/tools/analyze.py finds nothing — the analyzer
    holds itself to its own rules."""
    findings = _lint().run([_PKG / "tools" / "analyze.py"])
    assert not findings, f"tpu-lint flags itself: {findings}"


def test_baseline_requires_reasons(tmp_path):
    eng = _lint()
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"entries": [
        {"rule": "wall-clock", "path": "paddle_tpu/x.py", "line": 3,
         "reason": "pre-existing; tracked in ISSUE 99"}]}))
    assert len(eng.load_baseline(good)) == 1
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"entries": [
        {"rule": "wall-clock", "path": "paddle_tpu/x.py"}]}))
    with pytest.raises(ValueError, match="no reason"):
        eng.load_baseline(bad)


def test_baseline_suppresses_matching_findings(tmp_path):
    eng = _lint()
    f = tmp_path / "wall.py"
    f.write_text("import time\nT = time.time()\n")
    findings = eng.run([f])
    assert [x.rule for x in findings] == ["wall-clock"]
    kept, n = eng.apply_baseline(findings, [
        {"rule": "wall-clock", "path": findings[0].path, "line": 2,
         "reason": "fixture"}])
    assert not kept and n == 1
    # line-mismatched entry does NOT suppress
    kept, n = eng.apply_baseline(findings, [
        {"rule": "wall-clock", "path": findings[0].path, "line": 99,
         "reason": "fixture"}])
    assert len(kept) == 1 and n == 0


def test_shipped_baseline_is_valid_and_lean():
    """The checked-in baseline parses, demands reasons, and every entry
    still suppresses something real (stale entries rot)."""
    eng = _lint()
    entries = eng.load_baseline(_REPO / "TPU_LINT_BASELINE.json")
    if not entries:
        return  # clean tree, empty baseline: the preferred state
    findings = eng.run([_PKG])
    # per entry, not in aggregate: one entry matching two findings must
    # not mask a sibling entry that matches none
    for e in entries:
        _, suppressed = eng.apply_baseline(findings, [e])
        assert suppressed, (
            f"stale baseline entry {e!r} no longer matches any "
            "finding — delete it")


# ------------------------------------------------- lock graph reality


@pytest.fixture(scope="module")
def repo_report():
    eng = _lint()
    findings, index, lock_pass, n_pragma = eng.analyze_paths([_PKG])
    return eng.build_report(findings, index, lock_pass,
                            pragma_suppressed=n_pragma)


def test_lock_graph_names_the_real_fleet_locks(repo_report):
    """Acceptance: the --json lock report names the ACTUAL locks of the
    fleet runtime — the RPC transport's state + dispatcher locks, the
    telemetry registry/tracer/flight locks, and the router tier's WAL
    (models/journal.py) and replica-server (models/remote.py) locks."""
    locks = set(repo_report["lock_graph"]["locks"])
    for expected in (
        "paddle_tpu/distributed/rpc.py::_state_lock",
        "paddle_tpu/distributed/rpc.py::_RpcState.lock",
        "paddle_tpu/core/telemetry.py::_Metric._lock",
        "paddle_tpu/core/telemetry.py::MetricsRegistry._lock",
        "paddle_tpu/core/telemetry.py::Tracer._lock",
        "paddle_tpu/core/telemetry.py::FlightRecorder._lock",
        "paddle_tpu/core/telemetry.py::_trace_lock",
        "paddle_tpu/models/journal.py::RequestJournal._lock",
        "paddle_tpu/models/remote.py::ReplicaServer._lock",
        "paddle_tpu/models/remote.py::ReplicaServer._fence_lock",
        "paddle_tpu/core/resilience.py::CircuitBreaker._lock",
    ):
        assert expected in locks, (
            f"fleet lock {expected} missing from the lock graph — the "
            f"registry sees {sorted(locks)}")
    kinds = repo_report["lock_graph"]["locks"]
    assert kinds["paddle_tpu/models/journal.py::RequestJournal._lock"][
        "kind"] == "RLock"


def test_repo_lock_graph_has_no_cycles(repo_report):
    assert repo_report["lock_graph"]["cycles"] == [], (
        "the shipped fleet lock graph has an ordering cycle — that IS "
        "a deadlock waiting for load")


def test_lock_alias_resolves_to_shared_lock(repo_report):
    """serving.py's `self._swap_lock = _swap_lock` aliases the jit
    module's swap lock — the registry must model them as ONE node (two
    nodes would hide a real cross-module ordering cycle)."""
    locks = set(repo_report["lock_graph"]["locks"])
    assert "paddle_tpu/jit/__init__.py::_swap_lock" in locks
    assert not any("serving.py" in lid and "_swap_lock" in lid
                   for lid in locks)


def test_injected_ordering_inversion_is_reported(tmp_path):
    """Acceptance: take the CLEAN lock fixture, invert the acquisition
    order in a copy of one method, and the cycle must be reported."""
    src = (_FIXTURES / "good_clean.py").read_text()
    clean = _lint().run([_FIXTURES / "good_clean.py"],
                        rules={"lock-order-cycle"})
    assert not clean
    inverted = src.replace(
        "    def m2(self):\n"
        "        with self.lock_a:\n"
        "            with self.lock_b:\n",
        "    def m2(self):\n"
        "        with self.lock_b:\n"
        "            with self.lock_a:\n")
    assert inverted != src, "fixture shape changed; update this test"
    f = tmp_path / "inverted_copy.py"
    f.write_text(inverted)
    findings = _lint().run([f], rules={"lock-order-cycle"})
    assert any(f_.rule == "lock-order-cycle"
               and "lock_a" in f_.why and "lock_b" in f_.why
               for f_ in findings), (
        f"injected inversion not reported: {findings}")


def test_three_lock_cycle_is_reported(tmp_path):
    """Pairwise inversions are not enough: A->B, B->C, C->A is a
    deadlock with every PAIR consistently ordered — the SCC detector
    must still report it."""
    f = tmp_path / "tri.py"
    f.write_text(
        "import threading\n"
        "\n"
        "\n"
        "class Tri:\n"
        "    def __init__(self):\n"
        "        self.a = threading.Lock()\n"
        "        self.b = threading.Lock()\n"
        "        self.c = threading.Lock()\n"
        "\n"
        "    def ab(self):\n"
        "        with self.a:\n"
        "            with self.b:\n"
        "                pass\n"
        "\n"
        "    def bc(self):\n"
        "        with self.b:\n"
        "            with self.c:\n"
        "                pass\n"
        "\n"
        "    def ca(self):\n"
        "        with self.c:\n"
        "            with self.a:\n"
        "                pass\n")
    findings = _lint().run([f], rules={"lock-order-cycle"})
    assert len(findings) == 1, findings
    assert "3 lock(s)" in findings[0].why
    for name in ("Tri.a", "Tri.b", "Tri.c"):
        assert name in findings[0].why


def test_blocking_in_bare_helper_called_under_lock(tmp_path):
    """The snapshot-then-block refactor gone wrong: the lock holder
    calls a helper whose sleep holds no lock of its own — the blocking
    still happens under the caller's lock and must be reported (at the
    call site, naming the helper's blocking line)."""
    f = tmp_path / "indirect.py"
    f.write_text(
        "import threading\n"
        "import time\n"
        "\n"
        "\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "\n"
        "    def helper(self):\n"
        "        time.sleep(1)\n"
        "\n"
        "    def api(self):\n"
        "        with self._lock:\n"
        "            self.helper()\n")
    findings = _lint().run([f], rules={"lock-blocking-call"})
    assert len(findings) == 1, findings
    assert findings[0].line == 14           # the call site under lock
    assert "helper" in findings[0].why and "sleep" in findings[0].why


def test_cycle_through_recursive_call_chain(tmp_path):
    """Transitive lock reachability must survive call cycles: a() takes
    l then calls b(), b() calls a() (recursion), api() takes h then
    calls b(), inverted() takes l then h — the h->l edge only exists
    through the a<->b cycle, and a memoizing DFS would drop it."""
    f = tmp_path / "recur.py"
    f.write_text(
        "import threading\n"
        "\n"
        "\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self.l = threading.Lock()\n"
        "        self.h = threading.Lock()\n"
        "\n"
        "    def a(self, n):\n"
        "        with self.l:\n"
        "            self.b(n)\n"
        "\n"
        "    def b(self, n):\n"
        "        if n:\n"
        "            self.a(n - 1)\n"
        "\n"
        "    def api(self):\n"
        "        with self.h:\n"
        "            self.b(3)\n"
        "\n"
        "    def inverted(self):\n"
        "        with self.l:\n"
        "            with self.h:\n"
        "                pass\n")
    findings = _lint().run([f], rules={"lock-order-cycle"})
    assert findings, "h->l edge through the a<->b recursion was dropped"
    # the recursion also self-reacquires the non-reentrant l (its own
    # finding); the l/h ordering cycle must be reported beside it
    assert any("R.l" in x.why and "R.h" in x.why for x in findings), (
        findings)


def test_self_reacquire_through_helper_call(tmp_path):
    """`with self._lock: self.helper()` where helper() takes the same
    non-reentrant lock deadlocks on first call — the edge must survive
    the interprocedural propagation (an RLock version must NOT fire)."""
    f = tmp_path / "reacquire.py"
    src = (
        "import threading\n"
        "\n"
        "\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "\n"
        "    def helper(self):\n"
        "        with self._lock:\n"
        "            pass\n"
        "\n"
        "    def api(self):\n"
        "        with self._lock:\n"
        "            self.helper()\n")
    f.write_text(src)
    findings = _lint().run([f], rules={"lock-order-cycle"})
    assert findings and "self-deadlock" in findings[0].why, findings
    g = tmp_path / "reentrant.py"
    g.write_text(src.replace("threading.Lock()", "threading.RLock()"))
    assert not _lint().run([g], rules={"lock-order-cycle"})


def test_syntax_error_exits_2_not_1(tmp_path, capsys):
    """A broken analysis run must be distinguishable from findings:
    SyntaxError propagates to library callers and exits 2 on the CLI."""
    f = tmp_path / "broken.py"
    f.write_text("def oops(:\n")
    eng = _lint()
    with pytest.raises(SyntaxError):
        eng.run([f])
    assert eng.main([str(f)]) == 2
    assert "cannot parse" in capsys.readouterr().err


def test_duplicate_basenames_keep_separate_pragma_maps(tmp_path):
    """Two out-of-tree files with the same basename must not share a
    pragma map: a/dup.py's pragma may not suppress b/dup.py's finding,
    and both findings must carry distinguishable paths."""
    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir()
    b.mkdir()
    (a / "dup.py").write_text(
        "import time\n"
        "T = time.time()  # tpu-lint: disable=wall-clock\n")
    (b / "dup.py").write_text("import time\nT = time.time()\n")
    findings = _lint().run([a, b], rules={"wall-clock"})
    assert len(findings) == 1, findings
    assert findings[0].path == "b/dup.py"


def test_empty_path_is_an_error_not_clean(tmp_path, capsys):
    """A typo'd path must exit 2 loudly, never 0-findings-clean — a
    misconfigured CI gate that lints nothing is worse than no gate."""
    eng = _lint()
    with pytest.raises(FileNotFoundError):
        eng.make_report([tmp_path / "no_such_dir"])
    assert eng.main([str(tmp_path / "no_such_dir")]) == 2
    assert "no such path" in capsys.readouterr().err
    # a typo'd path MIXED with valid ones must also fail, not silently
    # lint half the gate
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    with pytest.raises(FileNotFoundError):
        eng.make_report([ok, tmp_path / "typo_dir"])


def test_baseline_accepts_bare_list_format(tmp_path):
    eng = _lint()
    p = tmp_path / "list.json"
    p.write_text(json.dumps([
        {"rule": "wall-clock", "path": "paddle_tpu/x.py",
         "reason": "legacy format entry"}]))
    assert len(eng.load_baseline(p)) == 1


def test_jit_entries_include_the_serving_programs(repo_report):
    names = {e["name"] for e in repo_report["jit_entries"]}
    assert "ContinuousBatchingEngine._build_programs.prefill" in names
    assert "ContinuousBatchingEngine._build_programs.segment" in names
    wrappers = {e["wrapper"] for e in repo_report["jit_entries"]}
    assert {"jit", "shard_map", "pallas_call"} <= wrappers


# ------------------------------------------------------------ CLI glue


def test_cli_json_report_schema(tmp_path, capsys):
    eng = _lint()
    rc = eng.main(["--json", str(_FIXTURES / "bad_except.py"),
                   "--rules", "bare-except-pass"])
    out = capsys.readouterr().out
    report = json.loads(out)
    assert rc == 1
    assert report["version"] == 1
    assert {"findings", "lock_graph", "jit_entries",
            "suppressed"} <= set(report)
    assert all(f["rule"] == "bare-except-pass"
               for f in report["findings"])
    assert len(report["findings"]) == 2


def test_cli_clean_run_exits_zero(capsys):
    eng = _lint()
    rc = eng.main([str(_PKG), "--baseline",
                   str(_REPO / "TPU_LINT_BASELINE.json")])
    capsys.readouterr()
    assert rc == 0


def test_cli_unknown_rule_is_an_error(capsys):
    assert _lint().main(["--rules", "no-such-rule", str(_FIXTURES)]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_obs_lint_renders_report(tmp_path, capsys):
    """The operator view: `obs lint REPORT.json` renders findings + the
    lock graph in the shared table format and propagates the verdict in
    its exit code."""
    from paddle_tpu.tools import obs

    eng = _lint()
    findings, index, lock_pass, n_pragma = eng.analyze_paths(
        [_FIXTURES / "bad_locks.py"])
    report = eng.build_report(findings, index, lock_pass,
                              pragma_suppressed=n_pragma)
    path = tmp_path / "report.json"
    path.write_text(json.dumps(report))
    rc = obs.main(["lint", str(path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "lock-order-cycle" in out
    assert "Inverted.lock_a" in out       # the lock graph table
    assert "CYCLES" in out


def test_obs_lint_clean_repo_exits_zero(capsys):
    from paddle_tpu.tools import obs

    rc = obs.main(["lint", str(_PKG)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "findings: none" in out
    assert "lock graph" in out
