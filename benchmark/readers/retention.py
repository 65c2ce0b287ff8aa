"""Per-layer metrics of the power-retention cell: the program's state
counters (``serving.state_*_total``; the decode segment's ride on its
``serving.device_wait`` span, the prefill's on their dispatch's span), its two
kernels found by name in the device trace, and the whole step's share of the
peak with this model's operations (``costs_retention.py``). Every reader
returns None where it finds nothing to read (a program without the counters,
an untraced run, a bag of another kind), never 0.
"""
from __future__ import annotations

import re

from benchmark import costs, costs_retention as C, stats
from benchmark import trace_reduce as T
from benchmark.readers import trace as R

SEGMENT = "^jit_segment"
PREFILL = "^jit_(prefill|chunk_step|final_chunk)"
PREFILL_SPANS = ("serving.prefill", "serving.chunked_prefill")
# the first array of a custom call's result: (rows, kv heads, group x chunk,
# d_v) of the chunk kernel
_OUT_SHAPE = re.compile(r"= \(?\w+\[(\d+),\d+,(\d+),\d+\]")


def _is_mine(bag):
    return bag.get("kind") == "serve" and \
        "gate_bias_range" in bag.get("model", {})


def _counter_delta(bag, name):
    if "snap0" not in bag or "snap1" not in bag:
        return None
    c0 = bag["snap0"].get("counters") or {}
    c1 = bag["snap1"].get("counters") or {}
    if name not in c1:
        return None
    return c1[name] - c0.get(name, 0)


def _traced_args(bag, names, keys):
    """Sum of the arguments ``keys`` over the spans named ``names`` that
    began inside the TRACED span: a kernel's time comes from there, so its
    bytes and operations have to come from there too."""
    from benchmark.readers import spans as S

    span = bag.get("trace_host_span")
    sink = S._sink(bag) if span else None
    if not sink or not S._complete_since(bag, span[0]):
        return None
    vals = [0] * len(keys)
    for e in sink:
        if e["name"] in names and span[0] <= e["t0"] < span[1]:
            for i, k in enumerate(keys):
                vals[i] += e["args"].get(k, 0)
    return vals


def _rows_per_layer_step_traced(bag):
    n = _traced_args(bag, ("serving.device_wait",),
                     ("state_rows_live", "state_layer_steps"))
    return n[0] / n[1] if n and n[1] else None


def state_rows_live(bag):
    """Live rows a layer a decode step, over the window's segments."""
    if not _is_mine(bag):
        return None
    rows = _counter_delta(bag, "serving.state_rows_live_total")
    steps = _counter_delta(bag, "serving.state_layer_steps_total")
    return rows / steps if rows is not None and steps else None


def state_bytes_per_slot(bag):
    """Bytes one slot's state holds over all layers, as the engine's
    ``kv_stats()`` reports them."""
    return bag.get("state_bytes_per_slot")


def serve_mfu_pct(bag):
    """Model FLOPs of all tokens processed in the window (2 x parameters a
    token + the recurrence, the head for output tokens only) over window x
    chips x the bf16 peak."""
    if not _is_mine(bag):
        return None
    m = bag["model"]
    w0, w1 = bag["window"]
    flops = 0.0
    for st in bag["stamps"]:
        if st.first is not None and w0 <= st.first < w1:
            flops += C.prefill_flops(m, st.prompt_len)
            flops += 2.0 * C.head_params(m)         # the first token's head
        before, inside = stats.token_counts(st, w0, w1)
        lo, hi = max(before, 1), inside     # token 0 came out of the prefill
        if hi > lo:
            flops += C.decode_flops(m, hi - lo)
    if not flops:
        return None
    peak = costs.peaks(bag["device_kind"])["bf16_flops"]
    return 100.0 * flops / ((w1 - w0) * bag["chips"] * peak)


def decode_hbm_roofline_pct(bag, per):
    """(weights + live rows x layers x 2 x state bytes over the HBM rate)
    over the device time of a step."""
    if not _is_mine(bag):
        return None
    step_ms = R.program_ms(bag, SEGMENT, per)
    rows = _rows_per_layer_step_traced(bag) if step_ms else None
    if not rows:
        return None
    least = C.decode_step_min_s(bag["model"], rows,
                                costs.peaks(bag["device_kind"]))
    return 100.0 * least / (1e-3 * step_ms)


def _kernel(bag, op, program):
    """(calls, seconds) of a named kernel inside the matching programs."""
    ev = bag.get("trace_events")
    if not ev or not _is_mine(bag):
        return None
    calls, secs = T.op_seconds(ev, op, program)
    return (calls, secs) if calls else None


def retention_decode_roofline_pct(bag, op):
    """One layer's decode kernel call against its live rows' state read
    and written once at the HBM rate."""
    k = _kernel(bag, op, SEGMENT)
    rows = _rows_per_layer_step_traced(bag) if k else None
    if not rows:
        return None
    least = C.retention_decode_min_s(bag["model"], rows,
                                     costs.peaks(bag["device_kind"]))
    return 100.0 * least / (k[1] / k[0])


def retention_decode_share_pct(bag, op):
    """The decode kernel's share of the segment programs' device time."""
    k = _kernel(bag, op, SEGMENT)
    if not k:
        return None
    _, secs = T.program_seconds(bag["trace_events"], SEGMENT)
    return 100.0 * k[1] / secs if secs else None


def _chunk_work(bag, op):
    """(real tokens x layers, seconds) of the chunk kernel's calls inside
    the prefill programs of the trace. The positions come from the calls
    themselves: a call is one layer's, and its output shape (rows, kv
    heads, group x chunk, d_v) says how many rows of how long a chunk it
    took. The share of them that was real comes from the prefill
    dispatches whose spans began inside the traced span: a span's tokens
    and the trace's calls are not the same dispatches at the two edges, a
    share over a few dozen of them hardly moves for it."""
    ev = bag.get("trace_events")
    if not ev or not _is_mine(bag):
        return None
    m = bag["model"]
    grp = m["num_attention_heads"] // m["num_key_value_heads"]
    rx, prx = re.compile(op), re.compile(PREFILL)
    positions = secs = 0.0
    for e in T.device_ops(ev):
        if rx.search(e["hlo"]) and prx.search(e["program"]):
            shape = _OUT_SHAPE.search(e["hlo"])
            if not shape:
                return None
            positions += int(shape[1]) * int(shape[2]) // grp
            secs += e["dur"]
    n = _traced_args(bag, PREFILL_SPANS, ("state_tokens", "state_padded")) \
        if positions else None
    if not n or not n[0]:
        return None
    nd = max(len(T.devices(ev)), 1)
    return positions * n[0] / (n[0] + n[1]) / nd, secs / nd


def retention_chunk_roofline_pct(bag, op):
    """The chunk kernel's calls in the prefill programs against the
    recurrence's FLOPs of the REAL tokens they fed (masked positions are
    the kernel's waste)."""
    work = _chunk_work(bag, op)
    if not work:
        return None
    least = C.retention_chunk_min_s(bag["model"], work[0],
                                    costs.peaks(bag["device_kind"]))
    return 100.0 * least / work[1]


def retention_chunk_us_per_tok(bag, op):
    """The chunk kernel's device microseconds a real token a layer."""
    work = _chunk_work(bag, op)
    return 1e6 * work[1] / work[0] if work else None
