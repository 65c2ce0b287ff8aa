"""Per-layer metrics read from the program's telemetry registry: the
harness snapshots it at the window's two ends and the readers subtract."""
from __future__ import annotations


def _hist_delta(bag, name):
    """(count, sum) a histogram series gained over the window."""
    if "snap0" not in bag:
        return 0, 0.0
    h0 = (bag["snap0"].get("histograms") or {}).get(name, {})
    h1 = (bag["snap1"].get("histograms") or {}).get(name, {})
    return (h1.get("count", 0) - h0.get("count", 0),
            h1.get("sum", 0.0) - h0.get("sum", 0.0))


def hist_mean_ms(bag, series):
    """Mean of the observations a histogram series took in the window."""
    count, total = _hist_delta(bag, series)
    return 1e3 * total / count if count else None


def phase_share_pct(bag, phases):
    """Seconds the scheduler spent in the given ``serving.phase_s`` phases
    over the window (host clock)."""
    total, seen = 0.0, 0
    for phase in phases:
        count, secs = _hist_delta(bag, f"serving.phase_s{{phase={phase}}}")
        total += secs
        seen += count
    if not seen:
        return None
    w0, w1 = bag["window"]
    return 100.0 * total / (w1 - w0)
