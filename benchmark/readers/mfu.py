"""The whole step's share of the chips' peak: model FLOPs of all tokens
processed in the window over (window x chips x peak)."""
from __future__ import annotations

from benchmark import costs, stats


def _share(bag, flops):
    w0, w1 = bag["window"]
    peak = costs.peaks(bag["device_kind"])["bf16_flops"]
    return 100.0 * flops / ((w1 - w0) * bag["chips"] * peak)


def serve_mfu_pct(bag):
    if bag["kind"] != "serve":
        return None
    m = bag["model"]
    w0, w1 = bag["window"]
    flops = 0.0
    for st in bag["stamps"]:
        if st.first is not None and w0 <= st.first < w1:
            flops += costs.prefill_flops(m, 0, st.prompt_len)
            flops += 2.0 * costs.head_params(m)     # the first token's head
        before, inside = stats.token_counts(st, w0, w1)
        lo, hi = max(before, 1), inside      # token 0 came out of the prefill
        if hi > lo:
            flops += costs.decode_flops(m, st.prompt_len + lo,
                                        st.prompt_len + hi)
    return _share(bag, flops) if flops else None


def train_mfu_pct(bag):
    if bag["kind"] != "train":
        return None
    flops = bag["steps"] * costs.train_flops_tokens(
        bag["model"], bag["batch"], bag["seq"])
    return _share(bag, flops)


def peak_hbm_gib(bag):
    peak = bag.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
