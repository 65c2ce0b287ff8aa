"""Training traffic: sequences packed from seeded documents.

Documents have heavy-tailed lengths (the mix's ``doc_length`` lognormal); each
is a run of seeded token ids closed by token id 0, and documents are laid end
to end and cut into sequences of ``seq_len`` tokens, as a pretraining pipeline
packs them. Attention is plain causal across a pack, as the repo trains today.
The stream is a pure function of ``--seed``: the reference regenerates the
first batches from it.
"""
from __future__ import annotations

import numpy as np


def doc_lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    raw = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def sequences(traffic: dict, seed: int, vocab: int):
    """Endless iterator of ``(seq_len,)`` int32 rows, every row different."""
    rng = np.random.default_rng(int(seed))
    seq_len = int(traffic["seq_len"])
    buf = np.empty((0,), np.int32)
    while True:
        while buf.size < seq_len:
            lens = doc_lengths(traffic["doc_length"], rng, 16)
            docs = []
            for n in lens:
                doc = rng.integers(1, vocab, int(n), dtype=np.int32)
                doc[-1] = 0          # end of document
                docs.append(doc)
            buf = np.concatenate([buf, *docs])
        yield buf[:seq_len].copy()
        buf = buf[seq_len:]


def batches(traffic: dict, seed: int, vocab: int, n: int) -> list[np.ndarray]:
    """The first ``n`` batches ``(batch, seq_len)`` of the stream."""
    it = sequences(traffic, seed, vocab)
    b = int(traffic["batch"])
    return [np.stack([next(it) for _ in range(b)]) for _ in range(n)]
