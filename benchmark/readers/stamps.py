"""Per-layer metrics read from the harness's own stamps and turn samples."""
from __future__ import annotations

import math

from benchmark import stats


def _finite_percentile(values, q):
    if not values:
        return None
    v = stats.percentile(values, q)
    return None if math.isinf(v) else v


def gen_late_ms(bag, q=90):
    """How late the generator ran: submit time minus due time."""
    if bag["kind"] != "serve":
        return None
    return _finite_percentile(
        stats.gen_late_each_ms(bag["stamps"], *bag["window"]), q)


def ttft_ms(bag, q=90):
    if bag["kind"] != "serve":
        return None
    return _finite_percentile(
        stats.ttft_each_ms(bag["stamps"], *bag["window"]), q)


def tpot_ms(bag, q=90):
    """Percentile over requests of each request's own time per token."""
    if bag["kind"] != "serve":
        return None
    return _finite_percentile(
        stats.tpot_each_ms(bag["stamps"], *bag["window"]), q)


def occupancy_pct(bag):
    """Mean share of the engine's slots in use over the window's turns."""
    samples = bag.get("samples")
    if not samples:
        return None
    return 100.0 * sum(s[1] for s in samples) / len(samples)


def kv_pages_peak_pct(bag):
    """Peak of the pool's pages granted over the pool, by turn."""
    samples = bag.get("samples")
    if not samples:
        return None
    return 100.0 * max(s[2] / s[3] for s in samples)
