import json
import math
import os
from collections import Counter

import numpy as np
import pytest

from benchmark.generators import packed_docs, stratified

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def load(name):
    base = TRAFFIC if os.path.exists(os.path.join(TRAFFIC, name + ".json")) \
        else os.path.join(HERE, "data")
    with open(os.path.join(base, name + ".json")) as f:
        return json.load(f)


def per_block(requests):
    blocks = {}
    for r in requests:
        blocks.setdefault(r["block"], []).append((len(r["prompt"]), r["max_new"]))
    return blocks


@pytest.mark.parametrize("name", ["chat-open", "tiny-chat"])
def test_every_seed_offers_the_same_load_in_every_block(name):
    t = load(name)
    a = per_block(stratified.generate(t, 3, 45.0, 32768))
    b = per_block(stratified.generate(t, 2 ** 31 + 11, 45.0, 32768))
    assert a.keys() == b.keys()
    first = Counter(a[0])
    for k in a:
        assert len(a[k]) == t["requests_per_block"]
        assert Counter(a[k]) == first == Counter(b[k])
    assert any(a[k] != b[k] for k in a), "the seed does not change the order"


@pytest.mark.parametrize("name", ["chat-open", "tiny-chat"])
def test_every_seed_offers_the_same_blocks_in_another_order(name):
    """Sizes AND arrival offsets: the window of any seed is the same set of
    block patterns; the ramp is the same blocks in the same order."""
    t = load(name)

    def blocks(seed):
        out = {}
        for r in stratified.generate(t, seed, 45.0, 32768):
            off = round(r["due_s"] - r["block"] * t["block_s"], 9)
            out.setdefault((r["block"], r["pattern"]), []).append(
                (off, len(r["prompt"]), r["max_new"]))
        return out

    a, b = blocks(3), blocks(2 ** 31 + 11)
    by_pattern = lambda x: {k[1]: v for k, v in x.items()}
    assert by_pattern(a) == by_pattern(b)
    assert [k[1] for k in sorted(a)] != [k[1] for k in sorted(b)]
    ramp = math.ceil(t["ramp_s"] / t["block_s"])
    assert [k[1] for k in sorted(a)][:ramp] == [k[1] for k in sorted(b)][:ramp] == list(range(ramp))
    # a longer window keeps the patterns of a shorter one
    short = {r["pattern"] for r in stratified.generate(t, 3, 20.0, 32768)}
    assert short == set(range(ramp + math.ceil(20.0 / t["block_s"])))


def test_open_loop_arrivals_are_inside_their_block_and_sorted():
    t = load("chat-open")
    reqs = stratified.generate(t, 5, 45.0, 32768)
    dues = [r["due_s"] for r in reqs]
    assert dues == sorted(dues)
    for r in reqs:
        assert r["block"] * t["block_s"] <= r["due_s"] < (r["block"] + 1) * t["block_s"]
    assert len({r["rid"] for r in reqs}) == len(reqs)
    other = stratified.generate(t, 6, 45.0, 32768)
    assert [r["due_s"] for r in other] != dues
    assert not np.array_equal(reqs[0]["prompt"][:8], other[0]["prompt"][:8])


def test_same_seed_gives_the_same_requests():
    t = load("chat-open")
    a = stratified.generate(t, 2 ** 31 + 3, 20.0, 32768)
    b = stratified.generate(t, 2 ** 31 + 3, 20.0, 32768)
    assert all(np.array_equal(x["prompt"], y["prompt"]) and x["due_s"] == y["due_s"]
               for x, y in zip(a, b))


def test_lengths_stay_inside_the_mix_limits():
    for name in ("chat-open", "tiny-chat"):
        t = load(name)
        for p, o in stratified.block_pairs(t):
            assert t["prompt"]["min"] <= p <= t["prompt"]["max"]
            assert t["output"]["min"] <= o <= t["output"]["max"]
            assert p + o <= 4096


def test_an_unknown_mode_is_refused():
    t = dict(load("tiny-chat"), mode="backlog")
    with pytest.raises(ValueError):
        stratified.generate(t, 4, 45.0, 256)


def test_packed_rows_are_seeded_and_all_differ():
    t = load("pretrain-packed-4k-b2")
    a = packed_docs.batches(t, 9, 92544, 3)
    b = packed_docs.batches(t, 9, 92544, 3)
    c = packed_docs.batches(t, 10, 92544, 3)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    rows = [tuple(r[:64]) for batch in a for r in batch]
    assert len(set(rows)) == len(rows) == 6
    assert a[0].shape == (2, 4096) and a[0].dtype == np.int32
    assert 0 <= a[0].min() and a[0].max() < 92544
