"""Metric arithmetic on the client's own stamps.

A serving run keeps, for every request it sent, one ``Stamp``: when it was due,
when it was submitted, and after each scheduler turn that showed new tokens
the pair (time the turn returned, tokens seen so far). Everything here is a
pure function of those stamps and the window ``[t0, t1)``; all times are the
harness's monotonic clock in seconds.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import median, quantiles


@dataclass
class Stamp:
    rid: int
    prompt_len: int
    max_new: int
    due: float | None            # set when the ramp starts
    submitted: float | None = None
    seen: list = field(default_factory=list)    # [(t, tokens so far)]
    status: str | None = None    # terminal status once known
    n_final: int | None = None   # tokens in the terminal result
    tokens: object = None        # the served token ids, once terminal

    @property
    def first(self):
        return self.seen[0][0] if self.seen else None


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation; an empty
    list has none."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no values")
    k = (len(vals) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if vals[lo] == vals[hi]:          # also keeps inf - inf out
        return vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (k - lo)


def token_counts(st: Stamp, t0: float, t1: float):
    """(tokens seen before ``t0``, tokens seen by the last turn inside
    ``[t0, t1)`` or 0 if none fell inside)."""
    before = inside = 0
    for t, n in st.seen:
        if t < t0:
            before = n
        elif t < t1:
            inside = n
    return before, inside


def tokens_in(st: Stamp, t0: float, t1: float) -> int:
    """Output tokens of ``st`` first seen inside ``[t0, t1)``."""
    before, inside = token_counts(st, t0, t1)
    return max(inside - before, 0)


def tpot_sums(stamps, t0: float, t1: float):
    """(decode seconds, decoded tokens) over every request whose first token
    was seen in the window: last token seen in the window minus the first,
    and the tokens seen by then less one."""
    secs, toks = 0.0, 0
    for st in stamps:
        if st.first is None or not (t0 <= st.first < t1):
            continue
        last_t, last_n = st.seen[0]
        for t, n in st.seen:
            if t < t1:
                last_t, last_n = t, n
        first_n = st.seen[0][1]
        if last_n > first_n:
            secs += last_t - st.first
            toks += last_n - first_n
    return secs, toks


def tpot_mean_ms(stamps, t0, t1):
    secs, toks = tpot_sums(stamps, t0, t1)
    return 1e3 * secs / toks if toks else None


def tpot_each_ms(stamps, t0, t1):
    out = []
    for st in stamps:
        secs, toks = tpot_sums([st], t0, t1)
        if toks:
            out.append(1e3 * secs / toks)
    return out


def ttft_each_ms(stamps, t0, t1):
    """First token seen minus due time for every request due in the window;
    a request that never showed a token counts as infinitely late."""
    out = []
    for st in stamps:
        if st.due is None or not (t0 <= st.due < t1):
            continue
        out.append(math.inf if st.first is None
                   else 1e3 * (st.first - st.due))
    return out


def gen_late_each_ms(stamps, t0, t1):
    return [1e3 * (st.submitted - st.due) for st in stamps
            if st.due is not None and st.submitted is not None
            and t0 <= st.due < t1]


def serve_tokens(stamps, t0, t1):
    """(prompt tokens of requests whose prefill completed in the window,
    output tokens seen in the window). A prefill is complete when the
    request's first token is seen."""
    prompt = sum(st.prompt_len for st in stamps
                 if st.first is not None and t0 <= st.first < t1)
    return prompt, sum(tokens_in(st, t0, t1) for st in stamps)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the builder's contract measures a spread."""
    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)
