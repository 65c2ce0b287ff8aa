"""Per-layer metrics of the ``nemotron_h`` cell: the program's state and
expert counters (ONE statistics vector a decode step, carried out on the
segment's ``serving.device_wait`` span and summed into
``serving.<name>_total``; the prefill's state counters on their dispatch's
span), its kernels found by name in the device trace, and the whole step's
share of the peak with this model's operations (``costs_nemotron_h.py``).
Every reader returns None where it finds nothing to read (a program without
the counters, an untraced run, a bag of another kind), never 0.
"""
from __future__ import annotations

import re

from benchmark import costs, costs_nemotron_h as C, stats
from benchmark import trace_reduce as T
from benchmark.readers import moe_mla as E
from benchmark.readers import retention as S
from benchmark.readers import trace as R

SEGMENT = S.SEGMENT
PREFILL = S.PREFILL
# the first array of the chunk kernel's result: (rows, chunk, heads x P)
_OUT_SHAPE = re.compile(r"= \(?\w+\[(\d+),(\d+),\d+\]")


def _is_mine(bag):
    return bag.get("kind") == "serve" and \
        "hybrid_override_pattern" in bag.get("model", {})


def state_rows_live(bag):
    """Live rows a Mamba-2 layer a decode step, over the window's
    segments."""
    if not _is_mine(bag):
        return None
    rows = S._counter_delta(bag, "serving.state_rows_live_total")
    steps = S._counter_delta(bag, "serving.state_layer_steps_total")
    return rows / steps if rows is not None and steps else None


def state_bytes_per_slot(bag):
    """Bytes one slot's state holds over all layers, as the engine's
    ``kv_stats()`` reports them."""
    return bag.get("state_bytes_per_slot") if _is_mine(bag) else None


def kv_bytes_per_token(bag):
    """Bytes one token keeps in pages over all layers, as the engine's
    ``kv_stats()`` reports them."""
    return bag.get("kv_bytes_per_token") if _is_mine(bag) else None


def experts_hit_per_layer(bag):
    """Held experts that some live token chose, an expert layer a decode
    step: what the grouped product reads."""
    return E.experts_hit_per_layer(bag) if _is_mine(bag) else None


def load_imbalance(bag):
    return E.load_imbalance(bag) if _is_mine(bag) else None


def serve_mfu_pct(bag):
    """Model FLOPs of all tokens processed in the window (the parameters a
    token activates HERE, the state-space form, causal attention in the
    attention layers, the head for output tokens) over window x chips x the
    bf16 peak."""
    if not _is_mine(bag):
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
    """(fixed weights + experts hit + live rows' state twice + live keys
    and values of one decode step over the HBM rate) over the device time
    of a step; every count from the traced span."""
    if not _is_mine(bag):
        return None
    step_ms = R.program_ms(bag, SEGMENT, per)
    n = E._moe_counts_traced(bag) if step_ms else None
    rows = S._rows_per_layer_step_traced(bag) if n else None
    live = R._mean_live(bag) if rows else None
    if not live or bag.get("kv_bytes_per_token") is None:
        return None
    least = C.decode_step_min_s(bag["model"], n[1] / n[3], rows, live[0],
                                bag["kv_bytes_per_token"],
                                costs.peaks(bag["device_kind"]))
    return 100.0 * least / (1e-3 * step_ms)


def _kernel(bag, op, program=SEGMENT):
    """(calls, seconds) of a named kernel inside the matching programs."""
    ev = bag.get("trace_events")
    if not ev or not _is_mine(bag):
        return None
    calls, secs = T.op_seconds(ev, op, program)
    return (calls, secs) if calls else None


def kernel_share_pct(bag, op):
    """A named kernel's share of the segment programs' device time."""
    k = _kernel(bag, op)
    if not k:
        return None
    _, secs = T.program_seconds(bag["trace_events"], SEGMENT)
    return 100.0 * k[1] / secs if secs else None


def ssd_decode_roofline_pct(bag, op):
    """One layer's decode kernel call against its live rows' state read
    and written once at the HBM rate."""
    k = _kernel(bag, op)
    rows = S._rows_per_layer_step_traced(bag) if k else None
    if not rows:
        return None
    least = C.ssd_decode_min_s(bag["model"], rows,
                               costs.peaks(bag["device_kind"]))
    return 100.0 * least / (k[1] / k[0])


def moe_gmm_roofline_pct(bag, op):
    """An expert layer's two grouped products a decode step against the
    larger of their bytes over the HBM rate and their operations over the
    peak; a pair of calls is one layer-step."""
    k = _kernel(bag, op)
    n = E._moe_counts_traced(bag) if k else None
    if not n:
        return None
    m = bag["model"]
    rows = (bag["config"]["deployment"]["engine"]["max_slots"]
            * m["num_experts_per_tok"])
    least = C.moe_gmm_min_s(m, n[1] / n[3], n[0] / n[3], rows,
                            costs.peaks(bag["device_kind"]))
    return 100.0 * least / (k[1] / (k[0] / 2.0))


def paged_attn_roofline_pct(bag, op):
    """One attention layer's paged decode kernel over the live keys and
    values (``readers/trace.py``'s reader: the page keeps (kv heads, head
    size) a token, 1,024 bytes a layer here), for this model alone."""
    return R.paged_attn_roofline_pct(bag, op, SEGMENT) if _is_mine(bag) \
        else None


def _chunk_work(bag, op):
    """(real tokens x layers, seconds) of the chunk kernel's calls inside
    the prefill programs of the trace. The positions come from the calls
    themselves: a call is one layer's, and its output shape (rows, chunk,
    heads x P) says how many rows of how long a chunk it took. The share of
    them that was real comes from the prefill dispatches whose spans began
    inside the traced span (``state_tokens``, ``state_padded``)."""
    ev = bag.get("trace_events")
    if not ev or not _is_mine(bag):
        return None
    rx, prx = re.compile(op), re.compile(PREFILL)
    positions = secs = 0.0
    for e in T.device_ops(ev):
        if rx.search(e["hlo"]) and prx.search(e["program"]):
            shape = _OUT_SHAPE.search(e["hlo"])
            if not shape:
                return None
            positions += int(shape[1]) * int(shape[2])
            secs += e["dur"]
    n = S._traced_args(bag, S.PREFILL_SPANS,
                       ("state_tokens", "state_padded")) if positions \
        else None
    if not n or not n[0]:
        return None
    nd = max(len(T.devices(ev)), 1)
    return positions * n[0] / (n[0] + n[1]) / nd, secs / nd


def ssd_chunk_roofline_pct(bag, op):
    """The chunk kernel's calls in the prefill programs against the chunked
    form's FLOPs of the REAL tokens they fed (masked positions are the
    kernel's waste)."""
    work = _chunk_work(bag, op)
    if not work:
        return None
    least = C.ssd_chunk_min_s(bag["model"], work[0],
                              costs.peaks(bag["device_kind"]))
    return 100.0 * least / work[1]


def ssd_chunk_us_per_tok(bag, op):
    """The chunk kernel's device microseconds a real token a layer."""
    work = _chunk_work(bag, op)
    return 1e6 * work[1] / work[0] if work else None
