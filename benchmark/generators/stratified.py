"""Serving traffic with an exact load: the one generator for every serving mix.

A mix is a data file (``benchmark/traffic/<name>.json``). Lengths are taken at
FIXED quantiles of the mix's two distributions, ``(i + 0.5) / n`` for the ``n``
requests of a block, and paired by the file's ``pairing_seed``; so every block
of every seed offers the same multiset of (prompt, output) pairs, hence the
same requests, prompt tokens and output tokens.

The blocks themselves are fixed too. Block pattern ``k`` (the order of the
pairs in the block and their arrival offsets: independent uniforms, so locally
Poisson) is drawn from ``pairing_seed``, not from ``--seed``. ``--seed``
decides the ORDER OF THE BLOCKS in the window and the token ids: every seed
offers the same set of sizes and arrivals in another order. (Arrival offsets
drawn from ``--seed`` made two seeds differ by 5 % in ``tpot_mean_ms`` where
two runs of one seed differed by 1 %: how arrivals clump decides how many
prefill dispatches and pipeline drains a window holds. So the cell measures
the file's own draw of clumpings, and a change whose gain depends on how
arrivals clump is seen only as far as these patterns show it. PERF.md, PR 24.)

``mode`` is ``open_loop``, the only one yet: ``requests_per_block`` requests in
every ``block_s`` seconds, each with its due time. The ramp's blocks are
patterns 0, 1, ... for every seed; the window's blocks are the following
patterns in the seed's order. (A closed backlog was tried for
``docs-saturated`` and taken out with that cell: PERF.md, Open questions.)
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of a clipped
    lognormal (``median``, ``sigma`` of the log, ``min``, ``max``)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def block_pairs(traffic: dict) -> list[tuple[int, int]]:
    """The (prompt, output) length pairs every block offers: the same for
    every seed. The pairing is a fixed permutation drawn from the file's
    ``pairing_seed``."""
    n = int(traffic["requests_per_block"])
    prompts = quantile_lengths(traffic["prompt"], n)
    outputs = quantile_lengths(traffic["output"], n)
    perm = np.random.default_rng(int(traffic["pairing_seed"])).permutation(n)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[perm])]


def rate_rps(traffic: dict) -> float:
    return traffic["requests_per_block"] / float(traffic["block_s"])


def block_patterns(traffic: dict, n_blocks: int) -> list[list[tuple]]:
    """Patterns 0 .. n_blocks-1, the same for every seed and independent of
    ``n_blocks``: each a list of ``(offset_s, prompt, output)`` in sending
    order."""
    pairs = block_pairs(traffic)
    n = len(pairs)
    rng = np.random.default_rng([int(traffic["pairing_seed"]), 1])
    block_s = float(traffic["block_s"])
    out = []
    for _ in range(n_blocks):
        order = rng.permutation(n)
        offsets = np.sort(rng.uniform(0.0, block_s, n))
        out.append([(float(offsets[j]), *pairs[k])
                    for j, k in enumerate(order)])
    return out


def generate(traffic: dict, seed: int, seconds: float, vocab: int) -> list[dict]:
    """Requests in sending order: ``{"rid", "due_s", "prompt", "max_new",
    "block", "pattern"}``. ``due_s`` counts from the start of the ramp. The
    blocks cover the ramp and ``seconds`` of window; the driver never sends a
    request that is due past the window's end."""
    if traffic["mode"] != "open_loop":
        raise ValueError(f"unknown traffic mode {traffic['mode']!r}")
    rng = np.random.default_rng(int(seed))
    block_s = float(traffic["block_s"])
    n_ramp = math.ceil(float(traffic["ramp_s"]) / block_s)
    n_blocks = n_ramp + math.ceil(seconds / block_s)
    patterns = block_patterns(traffic, n_blocks)
    order = list(range(n_ramp)) + [
        n_ramp + int(i) for i in rng.permutation(n_blocks - n_ramp)]
    out = []
    for b, k in enumerate(order):
        for offset, p_len, o_len in patterns[k]:
            out.append({
                "rid": len(out), "block": b, "pattern": k,
                "due_s": b * block_s + offset,
                "prompt": rng.integers(0, vocab, p_len, dtype=np.int32),
                "max_new": o_len,
            })
    return out


def summary(traffic: dict) -> dict:
    """What one block offers (identical for every seed)."""
    pairs = block_pairs(traffic)
    return {
        "requests_per_block": len(pairs),
        "prompt_tokens_per_block": sum(p for p, _ in pairs),
        "output_tokens_per_block": sum(o for _, o in pairs),
        "longest_prompt": max(p for p, _ in pairs),
        "longest_total": max(p + o for p, o in pairs),
        "rate_rps": rate_rps(traffic),
    }
