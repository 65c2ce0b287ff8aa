"""Per-layer metrics of the sparse-expert / latent-attention cell: the
program's expert counters (``serving.moe_*_total``, fed from the decode
segment's statistics), its two kernels found by name in the device trace, and
the whole step's share of the peak with this model's operations
(``costs_moe_mla.py``). Every reader returns None where it finds nothing to
read (a program without the counters, an untraced run, a bag of another
kind), never 0.
"""
from __future__ import annotations

from benchmark import costs, costs_moe_mla as C, stats
from benchmark import trace_reduce as T
from benchmark.readers import trace as R

SEGMENT = "^jit_segment"


def _counter_delta(bag, name):
    if "snap0" not in bag or "snap1" not in bag:
        return None
    c0 = bag["snap0"].get("counters") or {}
    c1 = bag["snap1"].get("counters") or {}
    if name not in c1:
        return None
    return c1[name] - c0.get(name, 0)


KEYS = ("assignments", "experts_hit", "load_max", "layer_steps")


def _moe_counts(bag):
    """(assignments, experts hit, summed largest load, layer steps) the
    window's decode segments counted, or None."""
    if bag.get("kind") != "serve":
        return None
    vals = [_counter_delta(bag, f"serving.moe_{k}_total") for k in KEYS]
    if any(v is None for v in vals) or not vals[3]:
        return None
    return vals


def _moe_counts_traced(bag):
    """The same four counts over the TRACED span alone: the segments whose
    fetch (``serving.device_wait``, which carries its segment's counts)
    began inside it. A kernel's time comes from the traced span, so its
    bytes have to come from there too: the live rows of five seconds
    differ from the window's mean by more than a roofline share allows."""
    from benchmark.readers import spans as S

    span = bag.get("trace_host_span")
    sink = S._sink(bag) if span else None
    if not sink or not S._complete_since(bag, span[0]):
        return None
    vals = [0, 0, 0, 0]
    for e in sink:
        if e["name"] != "serving.device_wait" or \
                not span[0] <= e["t0"] < span[1]:
            continue
        for i, k in enumerate(KEYS):
            vals[i] += e["args"].get("moe_" + k, 0)
    return vals if vals[3] else None


def experts_hit_per_layer(bag):
    """Routed experts that some live token chose, a sparse layer a decode
    step: what the grouped product reads."""
    n = _moe_counts(bag)
    return n[1] / n[3] if n else None


def load_imbalance(bag):
    """Rows of the busiest expert over the mean rows of an expert that was
    hit, a sparse layer a decode step."""
    n = _moe_counts(bag)
    if not n or not n[0] or not n[1]:
        return None
    return (n[2] / n[3]) / (n[0] / n[1])


def kv_bytes_per_token(bag):
    """Bytes one token keeps in the cache over all layers, as the engine's
    ``kv_stats()`` reports them."""
    return bag.get("kv_bytes_per_token")


def serve_mfu_pct(bag):
    """Model FLOPs of all tokens processed in the window (the parameters a
    token activates, the head for output tokens, causal attention in the
    plain form) over window x chips x the bf16 peak."""
    if bag.get("kind") != "serve" or "experts_held" not in bag.get("model",
                                                                  {}):
        return None
    m = bag["model"]
    w0, w1 = bag["window"]
    flops = 0.0
    for st in bag["stamps"]:
        if st.first is not None and w0 <= st.first < w1:
            flops += C.prefill_flops(m, 0, st.prompt_len)
            flops += 2.0 * C.head_params(m)         # the first token's head
        before, inside = stats.token_counts(st, w0, w1)
        lo, hi = max(before, 1), inside     # token 0 came out of the prefill
        if hi > lo:
            flops += C.decode_flops(m, st.prompt_len + lo,
                                    st.prompt_len + hi)
    if not flops:
        return None
    peak = costs.peaks(bag["device_kind"])["bf16_flops"]
    return 100.0 * flops / ((w1 - w0) * bag["chips"] * peak)


def decode_hbm_roofline_pct(bag, per):
    """(fixed weights + experts hit + live latent cache of one decode step
    over the HBM rate) over the device time of a step."""
    step_ms = R.program_ms(bag, SEGMENT, per)
    n = _moe_counts_traced(bag)
    live = R._mean_live(bag) if n else None
    if not step_ms or not live or not bag.get("kv_bytes_per_token"):
        return None
    least = C.decode_step_min_s(bag["model"], n[1] / n[3], live[0],
                                bag["kv_bytes_per_token"],
                                costs.peaks(bag["device_kind"]))
    return 100.0 * least / (1e-3 * step_ms)


def _kernel(bag, op):
    """(calls, seconds) of a named kernel inside the segment programs."""
    ev = bag.get("trace_events")
    if not ev or bag.get("kind") != "serve":
        return None
    calls, secs = T.op_seconds(ev, op, SEGMENT)
    return (calls, secs) if calls else None


def moe_gmm_roofline_pct(bag, op):
    """A sparse layer's two grouped products a decode step against the
    larger of their bytes over the HBM rate and their operations over the
    peak; a pair of calls is one layer-step."""
    k, n = _kernel(bag, op), _moe_counts_traced(bag)
    if not k or not n:
        return None
    m = bag["model"]
    rows = (bag["config"]["deployment"]["engine"]["max_slots"]
            * m["num_experts_per_tok"])
    least = C.moe_gmm_min_s(m, n[1] / n[3], n[0] / n[3], rows,
                            costs.peaks(bag["device_kind"]))
    return 100.0 * least / (k[1] / (k[0] / 2.0))


def moe_gmm_share_pct(bag, op):
    """The grouped products' share of the segment programs' device time."""
    k = _kernel(bag, op)
    if not k:
        return None
    _, secs = T.program_seconds(bag["trace_events"], SEGMENT)
    return 100.0 * k[1] / secs if secs else None


def paged_mla_roofline_pct(bag, op):
    """One layer's decode attention over the live latent cache against the
    larger of its bytes over the HBM rate and its operations over the
    peak."""
    k = _kernel(bag, op)
    live = R._mean_live(bag) if k else None
    if not live:
        return None
    slots = bag["config"]["deployment"]["engine"]["max_slots"]
    least = C.paged_mla_min_s(bag["model"], live[0], slots,
                              costs.peaks(bag["device_kind"]))
    return 100.0 * least / (k[1] / k[0])
