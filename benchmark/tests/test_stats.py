import math

from benchmark import stats
from benchmark.stats import Stamp


def decoding(rid, first, n, gap, due=None, prompt=100, stall_at=None, stall=0.0):
    st = Stamp(rid, prompt, n, due, submitted=due)
    t = first
    st.seen.append((t, 1))
    for k in range(1, (n - 2) // 16 + 2):
        t += 16 * gap + (stall if stall_at == k else 0.0)
        st.seen.append((t, min(1 + 16 * k, n)))
    return st


def test_tpot_mean_is_a_ratio_of_whole_window_sums_and_a_stall_moves_it():
    calm = [decoding(i, 1.0 + i, 65, 0.02) for i in range(10)]
    base = stats.tpot_mean_ms(calm, 0.0, 100.0)
    assert abs(base - 20.0) < 1e-6
    one_stalled = calm[:9] + [decoding(9, 10.0, 65, 0.02, stall_at=2, stall=1.0)]
    moved = stats.tpot_mean_ms(one_stalled, 0.0, 100.0)
    assert abs(moved - (10 * 64 * 20.0 + 1000.0) / (10 * 64)) < 1e-6
    # the median over requests, PR 22's statistic, does not see it
    assert abs(stats.percentile(stats.tpot_each_ms(one_stalled, 0, 100), 50) - 20.0) < 1e-6


def test_only_what_is_seen_inside_the_window_counts():
    st = decoding(0, 9.0, 65, 0.02)         # turns at 9.0, 9.32, 9.64, 9.96, 10.28
    secs, toks = stats.tpot_sums([st], 0.0, 10.0)
    assert toks == 48 and abs(secs - 0.96) < 1e-9
    assert stats.tokens_in(st, 0.0, 10.0) == 49
    assert stats.tokens_in(st, 9.5, 10.0) == 32
    early = decoding(1, -1.0, 65, 0.02)     # first token before the window opens
    assert stats.tpot_sums([early], 0.0, 10.0) == (0.0, 0)
    # ... and its last 16 tokens are seen at 0.28, inside it
    assert stats.serve_tokens([st, early], 0.0, 10.0) == (100, 49 + 16)


def test_ttft_counts_from_the_due_time_and_a_silent_request_is_infinitely_late():
    a = decoding(0, 1.5, 20, 0.02, due=1.0)
    b = Stamp(1, 10, 5, due=2.0, submitted=2.3)
    c = decoding(2, 50.0, 20, 0.02, due=49.9)       # due outside
    vals = stats.ttft_each_ms([a, b, c], 0.0, 10.0)
    assert vals[0] == 500.0 and math.isinf(vals[1]) and len(vals) == 2
    assert stats.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert [round(x) for x in stats.gen_late_each_ms([a, b], 0.0, 10.0)] == [0, 300]


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 90) == 5
    vals = [10.0, 10.1, 9.9, 10.2, 9.8, 10.0]
    from statistics import quantiles, median
    q = quantiles(vals, n=4)
    assert stats.spread(vals) == (q[2] - q[0]) / median(vals)
