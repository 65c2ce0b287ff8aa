"""Bench-trend regression harness over the checked-in ``BENCH_*`` series.

Seven bench rounds are checked into the repo root
(``BENCH_BASELINE.json`` + ``BENCH_r01..``) and until now nothing read
them as a SERIES: ``decode_tok_s_vs_floor`` regressed to 0.81x of its
recorded baseline at r05 and no tool flagged it. This module parses
every round — tolerating the real-world schema drift the files exhibit
(early rounds carry a ``parsed`` dict, later ones only a truncated
stdout ``tail``; the key set grew every round; r06 is a CPU-only smoke
whose absolute numbers are incomparable to the TPU points) — and
reports:

* **calibrated regressions**: each round's self-reported
  ``e2e_vs_baseline`` ratios (metric per in-run matmul TFLOP/s vs the
  then-current baseline — congestion-invariant by construction) below
  ``--ratio-threshold`` (default 0.9);
* **trend regressions**: a comparable round's calibrated metric falling
  more than ``--factor`` (default 1.5x, bench.py's own gate) below the
  best earlier comparable round;
* **gate violations**: the absolute overhead gates the benches declare
  (router < 5%, rpc < 10%, journal < 5%, telemetry < 3%, perfwatch
  < 3%) — these are relative measurements, so CPU smoke rounds count
  too.

Pure stdlib on purpose: the repo-root wrapper (``tools/bench_trend.py``)
loads this file directly so CI can run the harness without importing
the framework (no jax, no device contact).

Usage::

    python tools/bench_trend.py [--root DIR] [--json OUT] [--md OUT]
    # exit 0 clean, 1 regressions/gate violations, 2 unparseable rounds
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

__all__ = ["load_round", "load_baseline", "collect", "analyze",
           "diff_rounds", "render_markdown", "main",
           "GATES", "DEFAULT_RATIO_THRESHOLD", "DEFAULT_TREND_FACTOR"]

# absolute overhead gates declared by bench.py sections e3-e6 (percent,
# of active processing time) — relative measurements, platform-agnostic
GATES = {
    "fleet_router_overhead_pct": 5.0,
    "fleet_rpc_overhead_pct": 10.0,
    "router_journal_overhead_pct": 5.0,
    "telemetry_overhead_pct": 3.0,
    "perfwatch_overhead_pct": 3.0,
    # not a percentage: ANY post-warmup XLA recompile in the bench
    # workload (bench e6 records the count) breaks the PR 5 invariant
    "perfwatch_serving_compiles": 1.0,
    # overload-control plane (bench e7, flash-crowd drill): the
    # autoscaler's decision loop must stay cheap, the fleet must not
    # overshoot the needed capacity by more than one replica, and the
    # brownout ladder must hold the goodput floor, never lose the
    # protected class, and fully recover after the crowd passes
    "autoscale_overhead_pct": 3.0,
    "autoscale_reaction_s": 120.0,   # alarm -> new replica SERVING
    "autoscale_overshoot_replicas": 2.0,
    "brownout_protected_loss_pct": 1.0,
    "brownout_floor_breach": 1.0,    # 0/1: goodput floor under target
    "brownout_unrecovered": 1.0,     # 0/1: stage did not return to 0
    # tensor-parallel serving (bench e8): the host cost of committing
    # dispatch operands onto the TP mesh must stay a small share of
    # active serving time, a group member death must recover (breaker
    # trip + bit-exact failover + all results delivered) well inside a
    # minute, and any lost request or stream divergence is a hard fail.
    # Older rounds lack the section entirely — absent metrics are
    # skipped, so the series stays parseable end to end.
    "tp_dispatch_overhead_pct": 10.0,
    "tp_member_death_recovery_s": 60.0,
    "tp_lost_requests": 1.0,         # 0/1+: requests lost in the drill
    "tp_stream_divergence": 1.0,     # 0/1: failover stream != reference
    # dynamic paged-KV allocator + prefix caching (bench e9). A
    # ("min", x) gate fails when the value lands BELOW x (the default
    # scalar form stays an upper bound). Pre-e9 rounds lack the section
    # — absent metrics are skipped, as for e8.
    "kv_admit_gain": ("min", 2.0),   # dynamic / static concurrency
    # the fragmentation DROP: granted-tail waste relative to what the
    # static one-full-sequence-per-slot layout wastes on the same
    # workload snapshot (< 1.0 = the allocator reclaimed real memory;
    # the absolute pct is workload/page-size dependent, the ratio isn't)
    "kv_frag_vs_static": 1.0,
    "prefix_prefill_speedup": ("min", 1.0),  # shared-prefix prefill A/B
    "prefix_hit_rate": ("min", 0.001),  # sharing actually engaged
    "kv_serving_compiles": 1.0,      # any compile through the allocator
    # disaggregated prefill/decode serving (bench e10): the KV
    # page-transfer hop must stay a small share of active processing,
    # client TTFT under the long-prompt burst must stay within 2x of
    # the colocated arm (CPU-noise headroom on an invariant that is
    # "no worse" in spirit), and ANY request lost to the hop is a hard
    # fail. Pre-e10 rounds lack the section — absent metrics skip.
    "transfer_overhead_pct": 10.0,
    "decode_ttft_p95_ratio": 2.0,
    "transfer_lost_requests": 1.0,   # 0/1+: requests lost in the A/B
    # decode megakernel (bench e11): the fused segment program must beat
    # the unfused arm on chip, and the blocking-fetch share of a decode
    # step (device_wait p50, fused/unfused) must not regress past noise.
    # Pre-e11 rounds lack the section — absent metrics skip.
    "decode_megakernel_speedup": ("min", 1.0),
    "megakernel_device_wait_ratio": 1.25,
    # the re-armed decode floor (PR 10 left it at 0.81x): a 3-tuple gate
    # ("min"/"max", bound, requires_metric) applies only to rounds that
    # CARRY requires_metric — the floor is re-gated at parity from the
    # first e11 round onward without failing every pre-megakernel round
    "decode_vs_streaming_floor": ("min", 1.0, "decode_megakernel_speedup"),
}

DEFAULT_RATIO_THRESHOLD = 0.9   # per-round e2e_vs_baseline alarm
DEFAULT_TREND_FACTOR = 1.5      # cross-round drop alarm (bench E2E_FACTOR)

# keys that are identification/bookkeeping, not metrics
_NON_METRICS = {"metric", "unit", "device", "platform", "n_params_m",
                "vs_baseline"}
# nested dicts worth flattening into the series (per-op microbench stays
# with its own in-bench gate; regression lists are reported verbatim)
_FLATTEN = {"e2e_vs_baseline": "e2e."}

# substrings marking lower-is-better metrics for the trend direction
_LOWER_BETTER = ("_ms", "_us", "overhead", "_error")

# only SELF-CALIBRATED metrics ride the cross-round trend check: raw
# absolutes (img/s, tok/s) swung between rounds with the state of the
# machine each round ran on — the per-round e2e_vs_baseline ratios are
# their run-condition-invariant channel. These are ratios against an in-run reference (streaming
# floor, chip peak, serial arm), so a drop is a real code regression.
_TREND_CALIBRATED = ("mfu_pct", "vs_streaming_floor", "vs_floor",
                     "pipeline_speedup", "mfu_vs_in_run_matmul",
                     "megakernel_speedup")


def _trendable(metric) -> bool:
    return any(s in metric for s in _TREND_CALIBRATED)


def _tail_json(tail):
    """Recover the bench result object from a truncated stdout tail:
    the driver keeps only the LAST bytes of stdout, so the object is
    either intact (``{...}``) or front-truncated at a key boundary
    (``"k": v, ...}`` — re-brace it). Returns (dict, how) or
    (None, None)."""
    if not tail:
        return None, None
    for candidate, how in ((tail, "tail"), ("{" + tail, "tail-braced")):
        try:
            obj = json.loads(candidate)
        except ValueError:
            continue
        if isinstance(obj, dict):
            return obj, how
    return None, None


def _flatten_metrics(obj) -> dict:
    """Numeric scalars (top-level + the declared nested families) —
    the per-round metric row of the trend series."""
    out = {}
    for k, v in obj.items():
        if k in _NON_METRICS:
            continue
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[k] = float(v)
        elif isinstance(v, dict) and k in _FLATTEN:
            pre = _FLATTEN[k]
            for kk, vv in v.items():
                if isinstance(vv, (int, float)) and not isinstance(vv, bool):
                    out[pre + kk] = float(vv)
    return out


def load_round(path) -> dict:
    """One ``BENCH_rNN.json`` driver record → a normalized row:
    ``{name, rc, note, platform, device, source, metrics, error}``.
    ``metrics`` is None only when the round genuinely recorded nothing
    (r01: empty tail); ``error`` marks an unreadable/undecodable file —
    the schema-drift failure this harness exists to catch."""
    name = os.path.splitext(os.path.basename(path))[0]
    row = {"name": name, "rc": None, "note": None, "platform": None,
           "device": None, "source": None, "metrics": None, "error": None}
    try:
        rec = json.load(open(path))
    except (OSError, ValueError) as e:
        row["error"] = f"unreadable: {e}"
        return row
    if not isinstance(rec, dict):
        row["error"] = f"expected a dict, got {type(rec).__name__}"
        return row
    row["rc"] = rec.get("rc")
    row["note"] = rec.get("note")
    parsed = rec.get("parsed")
    how = "parsed"
    if not isinstance(parsed, dict):
        parsed, how = _tail_json(rec.get("tail") or "")
    if parsed is None:
        if rec.get("tail"):
            row["error"] = "tail present but not recoverable as JSON"
        return row  # empty round (no bench output): data-free, not broken
    row["source"] = how
    row["platform"] = parsed.get("platform")
    row["device"] = parsed.get("device")
    row["metrics"] = _flatten_metrics(parsed)
    return row


def load_baseline(path) -> dict:
    """``BENCH_BASELINE.json`` → ``{metrics, device, platform}`` (the
    auto-re-recorded calibrated-ratio record bench.py section (g)
    maintains)."""
    rec = json.load(open(path))
    meta = rec.get("_meta", {})
    device = str(meta.get("device", ""))
    return {
        "metrics": {k: float(v) for k, v in rec.get("metrics", {}).items()
                    if isinstance(v, (int, float))},
        "device": device,
        "platform": "cpu" if "cpu" in device.lower() else "tpu",
    }


def collect(root) -> dict:
    """Load the baseline + every round under ``root``, rounds sorted by
    name (r01, r02, ...)."""
    rounds = [load_round(p) for p in
              sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))]
    bl_path = os.path.join(root, "BENCH_BASELINE.json")
    baseline = load_baseline(bl_path) if os.path.exists(bl_path) else None
    return {"baseline": baseline, "rounds": rounds}


def _series(rounds, comparable) -> dict:
    """metric -> {round name: value} over the comparable rounds."""
    out: dict[str, dict] = {}
    for r in rounds:
        if r["name"] not in comparable or not r["metrics"]:
            continue
        for k, v in r["metrics"].items():
            out.setdefault(k, {})[r["name"]] = v
    return out


def analyze(root, ratio_threshold=DEFAULT_RATIO_THRESHOLD,
            trend_factor=DEFAULT_TREND_FACTOR) -> dict:
    """The full report over one repo root. Regression entries carry
    ``kind`` (calibrated | trend | gate), the metric, the round, and the
    numbers behind the verdict."""
    data = collect(root)
    baseline = data["baseline"]
    rounds = data["rounds"]
    base_platform = baseline["platform"] if baseline else None
    parse_errors = [{"round": r["name"], "error": r["error"]}
                    for r in rounds if r["error"]]
    empty = [r["name"] for r in rounds
             if not r["error"] and r["metrics"] is None]
    # comparable = rounds whose absolute/calibrated numbers share the
    # baseline's platform (r06's CPU smoke must not read as a 5x
    # regression against TPU points)
    comparable, incomparable = [], []
    for r in rounds:
        if not r["metrics"]:
            continue
        if (base_platform is None or r["platform"] is None
                or r["platform"] == base_platform):
            comparable.append(r["name"])
        else:
            incomparable.append(
                {"round": r["name"], "platform": r["platform"],
                 "baseline_platform": base_platform,
                 "note": r["note"]})
    regressions = []
    # (1) per-round calibrated ratios (the round's own congestion-
    # invariant comparison against its then-current baseline)
    for r in rounds:
        if not r["metrics"] or r["name"] not in comparable:
            continue
        for k, v in sorted(r["metrics"].items()):
            if k.startswith("e2e.") and v < ratio_threshold:
                regressions.append({
                    "kind": "calibrated", "round": r["name"],
                    "metric": k[len("e2e."):], "ratio": round(v, 3),
                    "threshold": ratio_threshold})
    # (2) cross-round trend on the comparable series
    series = _series(rounds, set(comparable))
    for metric, vals in sorted(series.items()):
        if (metric.startswith("e2e.") or len(vals) < 2
                or not _trendable(metric)):
            continue
        names = sorted(vals)
        latest = vals[names[-1]]
        prev = [vals[n] for n in names[:-1]]
        lower_better = any(s in metric for s in _LOWER_BETTER)
        if lower_better:
            best = min(prev)
            bad = best > 0 and latest > best * trend_factor
            ratio = latest / best if best else None
        else:
            best = max(prev)
            bad = latest > 0 and best > latest * trend_factor
            ratio = latest / best if best else None
        if bad:
            regressions.append({
                "kind": "trend", "round": names[-1], "metric": metric,
                "ratio": round(ratio, 3), "best_prior": best,
                "latest": latest, "factor": trend_factor})
    # (3) absolute overhead gates (relative measurements: every round)
    gate_violations = []
    for r in rounds:
        for gate, limit in GATES.items():
            v = (r["metrics"] or {}).get(gate)
            if v is None:
                continue
            if isinstance(limit, tuple):
                op, bound = limit[0], limit[1]
                # conditional gate: armed only for rounds carrying the
                # witness metric (a gate re-tightened mid-series must
                # not retroactively fail the rounds before the work)
                if len(limit) > 2 and (r["metrics"] or {}).get(
                        limit[2]) is None:
                    continue
            else:
                op, bound = "max", limit
            bad = (v < bound) if op == "min" else (v >= bound)
            if bad:
                gate_violations.append({
                    "kind": "gate", "round": r["name"], "metric": gate,
                    "value": v, "limit": bound, "op": op})
    return {
        "root": os.path.abspath(root),
        "baseline": ({"device": baseline["device"],
                      "platform": baseline["platform"],
                      "metrics": baseline["metrics"]}
                     if baseline else None),
        "rounds": [{k: r[k] for k in
                    ("name", "rc", "note", "platform", "source")}
                   | {"n_metrics": len(r["metrics"] or {})}
                   for r in rounds],
        "empty_rounds": empty,
        "incomparable": incomparable,
        "parse_errors": parse_errors,
        "series": series,
        "regressions": regressions,
        "gate_violations": gate_violations,
    }


def diff_rounds(a_path, b_path) -> list:
    """Metric-by-metric comparison of two bench records (round files or
    the baseline): ``[(metric, a, b, b/a), ...]`` over the keys both
    carry — the ``obs bench-diff`` backend."""
    def metrics_of(path):
        if os.path.basename(path).startswith("BENCH_BASELINE"):
            return load_baseline(path)["metrics"]
        r = load_round(path)
        if r["error"]:
            raise ValueError(f"{path}: {r['error']}")
        return r["metrics"] or {}

    am, bm = metrics_of(a_path), metrics_of(b_path)
    rows = []
    for k in sorted(set(am) & set(bm)):
        a, b = am[k], bm[k]
        rows.append((k, a, b, (b / a) if a else None))
    return rows


def render_markdown(report) -> str:
    """Human-readable report: round inventory, per-metric series over
    the comparable rounds, and every finding."""
    lines = ["# Bench trend report", ""]
    lines.append(f"Root: `{report['root']}`")
    if report["baseline"]:
        lines.append(f"Baseline device: {report['baseline']['device']} "
                     f"({report['baseline']['platform']})")
    lines += ["", "## Rounds", "",
              "| round | rc | source | platform | metrics | note |",
              "|---|---|---|---|---|---|"]
    for r in report["rounds"]:
        lines.append(
            f"| {r['name']} | {r['rc']} | {r['source'] or '—'} | "
            f"{r['platform'] or '—'} | {r['n_metrics']} | "
            f"{(r['note'] or '')[:60]} |")
    findings = (report["parse_errors"] + report["regressions"]
                + report["gate_violations"])
    lines += ["", f"## Findings ({len(findings)})", ""]
    if not findings:
        lines.append("No regressions, gate violations, or parse errors.")
    for e in report["parse_errors"]:
        lines.append(f"- **parse error** {e['round']}: {e['error']}")
    for e in report["regressions"]:
        if e["kind"] == "calibrated":
            lines.append(
                f"- **calibrated regression** `{e['metric']}` at "
                f"{e['round']}: {e['ratio']}x of baseline "
                f"(< {e['threshold']})")
        else:
            lines.append(
                f"- **trend regression** `{e['metric']}` at {e['round']}: "
                f"{e['ratio']}x of best prior ({e['best_prior']:g} -> "
                f"{e['latest']:g}, factor {e['factor']})")
    for e in report["gate_violations"]:
        cmp_ = "<" if e.get("op") == "min" else ">="
        lines.append(
            f"- **gate violation** `{e['metric']}` at {e['round']}: "
            f"{e['value']:g} {cmp_} {e['limit']:g}")
    if report["incomparable"]:
        lines += ["", "## Incomparable rounds", ""]
        for e in report["incomparable"]:
            lines.append(
                f"- {e['round']}: platform {e['platform']} vs baseline "
                f"{e['baseline_platform']} — absolutes skipped "
                f"({(e['note'] or '')[:80]})")
    key_metrics = sorted(k for k in report["series"]
                         if k.startswith("e2e.") or k in GATES)
    if key_metrics:
        rounds = [r["name"] for r in report["rounds"]]
        lines += ["", "## Key series", "",
                  "| metric | " + " | ".join(rounds) + " |",
                  "|---|" + "---|" * len(rounds)]
        for m in key_metrics:
            vals = report["series"][m]
            lines.append(
                f"| {m} | " + " | ".join(
                    f"{vals[r]:g}" if r in vals else "—"
                    for r in rounds) + " |")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_trend",
        description="Flag metric regressions across the checked-in "
                    "BENCH_* rounds")
    ap.add_argument("--root", default=None,
                    help="repo root holding BENCH_*.json (default: the "
                         "directory above tools/, else cwd)")
    ap.add_argument("--json", dest="json_out", default=None,
                    help="write the full report as JSON here")
    ap.add_argument("--md", dest="md_out", default=None,
                    help="write the markdown report here")
    ap.add_argument("--ratio-threshold", type=float,
                    default=DEFAULT_RATIO_THRESHOLD)
    ap.add_argument("--factor", type=float, default=DEFAULT_TREND_FACTOR)
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)
    root = args.root
    if root is None:
        here = os.path.dirname(os.path.abspath(__file__))
        for cand in (os.path.dirname(os.path.dirname(here)),
                     os.path.dirname(here), os.getcwd()):
            if glob.glob(os.path.join(cand, "BENCH_r*.json")):
                root = cand
                break
        else:
            root = os.getcwd()
    report = analyze(root, ratio_threshold=args.ratio_threshold,
                     trend_factor=args.factor)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(report, f, indent=1)
    md = render_markdown(report)
    if args.md_out:
        with open(args.md_out, "w") as f:
            f.write(md)
    if not args.quiet:
        sys.stdout.write(md)
    if report["parse_errors"]:
        return 2
    if report["regressions"] or report["gate_violations"]:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
