"""Per-layer metrics read from the device trace (``trace_reduce`` events).

Every reader returns None where the traced run left nothing for it to read;
none returns 0 for a share. Times are device times; operations and bytes
come from ``costs.py`` and the configuration's sizes.
"""
from __future__ import annotations

from benchmark import costs
from benchmark import trace_reduce as T


def _events(bag):
    return bag.get("trace_events") or None


def _traced_span(bag):
    """The traced part of the window on the harness's clock."""
    return bag.get("trace_host_span")


def device_idle_pct(bag):
    ev = _events(bag)
    win = T.traced_window(ev) if ev else None
    if not win:
        return None
    return 100.0 * (1.0 - T.busy_seconds(ev) / (win[1] - win[0]))


def program_ms(bag, program, per=1):
    """Device milliseconds of one execution of the matching programs,
    divided by ``per`` (a decode segment holds ``per`` steps)."""
    ev = _events(bag)
    if not ev:
        return None
    runs, secs = T.program_seconds(ev, program)
    return 1e3 * secs / runs / per if runs else None


def prefill_us_per_token(bag, program):
    """Device time of the prefill programs over the prompt tokens whose
    prefill completed inside the traced span."""
    ev, span = _events(bag), _traced_span(bag)
    if not ev or not span or bag["kind"] != "serve":
        return None
    _, secs = T.program_seconds(ev, program)
    tokens = sum(st.prompt_len for st in bag["stamps"]
                 if st.first is not None and span[0] <= st.first < span[1])
    return 1e6 * secs / tokens if tokens and secs else None


def _mean_live(bag):
    """(mean live tokens, mean active slots) over the turns of the traced
    span that had a sequence decoding."""
    span = _traced_span(bag)
    rows = [s for s in bag.get("samples", [])
            if span and span[0] <= s[0] < span[1] and s[5] > 0]
    if not rows:
        return None
    return (sum(s[4] for s in rows) / len(rows),
            sum(s[5] for s in rows) / len(rows))


def decode_hbm_roofline_pct(bag, program, per):
    """(weights + live KV of one decode step over the HBM rate) over the
    device time of a step."""
    step_ms = program_ms(bag, program, per)
    live = _mean_live(bag) if bag["kind"] == "serve" else None
    if not step_ms or not live:
        return None
    least = costs.decode_step_min_s(bag["model"], live[0],
                                    costs.peaks(bag["device_kind"]))
    return 100.0 * least / (1e-3 * step_ms)


def paged_attn_roofline_pct(bag, op, program):
    ev = _events(bag)
    live = _mean_live(bag) if ev and bag["kind"] == "serve" else None
    if not live:
        return None
    calls, secs = T.op_seconds(ev, op, program)
    if not calls:
        return None
    least = costs.paged_attn_min_s(bag["model"], live[0], live[1],
                                   costs.peaks(bag["device_kind"]))
    return 100.0 * least / (secs / calls)


def flash_roofline_pct(bag, op, program, backward=False):
    """One layer's causal flash attention at the train step's shapes."""
    ev = _events(bag)
    if not ev or bag["kind"] != "train":
        return None
    calls, secs = T.op_seconds(ev, op, program)
    if not calls:
        return None
    least = costs.flash_min_s(bag["model"], bag["batch"], bag["seq"],
                              costs.peaks(bag["device_kind"]), backward)
    # the backward pass is two kernels (dq; dk and dv): a pair is one call
    per_call = secs / (calls / 2 if backward else calls)
    return 100.0 * least / per_call
