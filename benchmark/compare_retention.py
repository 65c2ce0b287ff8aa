"""``compare.py``'s twin for the power-retention model: the chat cell's rule
(a seeded sample of the requests the window finished, the longest in it; the
plain reference's logits at the rows that produced the served tokens; the
widest and the mean gap of a served token's logit below the reference's best)
against ``reference/power_retention_plain.py``, whose weights come a layer at
a time from ``weights_retention.provider``. Prefill and then decode through
the state are so held to the reference's full forward in the attention form.

The logits are dominated by the bfloat16 projections and cannot see the
precision of the state itself, so four numbers read the STATE: the engine the
window ran on serves the sample's longest request and one more again
(``program_retention.served_states``) and what each leaves in its slot is
compared with the reference's float32 sum over the same tokens, over the whole
outer product, as the relative miss (Frobenius) of the worst kv head:
``state_s_gap`` / ``state_z_gap`` over every layer, ``state_s_gap_first`` /
``state_z_gap_first`` in the first layer alone. The first layer's input is the
embedding, the same on both sides, so there the miss is the layer's own
bfloat16 projections and the state's arithmetic; deeper layers add the drift
of the bfloat16 hidden states, which grows a layer and hides a state kept in
bfloat16 from the fourth layer on (``PERF.md`` has the readings). The kernels
and the arrays are the same for every layer.

The control tool asks besides for the same numbers of the reference computed
in float8 (``control_mm``), of the planted fault "the carried state dropped
at every chunk boundary" (``state_dropped``: a token then sees only its own
chunk of the engine's prompt-chunk size) and of a state KEPT in bfloat16
(``bf16_state``: rounded at every chunk boundary of the prompt and at every
decode step, where an engine would write it).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark import compare, program_retention
from benchmark import weights_retention as W
from benchmark.reference import power_retention_plain as ref

pick_sample = compare.pick_sample
# requests whose state is read back: the sample's first is its longest
STATE_REQUESTS = 2
# reference sequences are padded to a multiple: the mix's longest request is
# 3-4 k tokens, so a run compiles the reference's layer a few times
PAD = 512


def faults(chunk: int) -> dict:
    """The planted forms of the reference, by name; ``chunk`` is the
    engine's prompt chunk, where the program carries the state."""
    return {"state_dropped": {"window": chunk},
            "bf16_state": {"chunk": chunk, "state_dtype": "bfloat16"}}


def request_logits(get, m, item, mm="f32", **form):
    """Reference logits at the rows that produced ``item``'s tokens, and
    what the recurrence holds once the last but one of them is fed (the
    last is never fed): ``(logits, [(S, Z) a layer])``."""
    prompt, toks = item["prompt"], item["tokens"]
    ids = np.concatenate([prompt, toks]).astype(np.int32)
    n = -(-len(ids) // PAD) * PAD
    ids = np.concatenate([ids, np.zeros(n - len(ids), np.int32)])
    rows = len(prompt) - 1 + np.arange(len(toks))
    logits, states = ref.forward_logits(
        get, m, ids, rows, mm, state_at=len(prompt) + len(toks) - 1,
        prefill=len(prompt), **form)
    return np.asarray(logits), states


def token_gaps(logits, toks):
    """Each served token's gap below the reference's best at its row."""
    return logits.max(-1) - logits[np.arange(len(toks)), toks]


def state_gaps(got, want):
    """(layers, 2): S's and Z's relative miss (Frobenius) of each layer's
    worst kv head; ``got`` and ``want`` a layer a pair ``(S (KV, d, d,
    d_v), Z (KV, d, d))``."""
    out = np.zeros((len(want), 2))
    for layer, (pair, ref_pair) in enumerate(zip(got, want)):
        for i, (g, w) in enumerate(zip(pair, ref_pair)):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            heads = g.shape[0]
            miss = np.linalg.norm((g - w).reshape(heads, -1), axis=1) \
                / np.linalg.norm(w.reshape(heads, -1), axis=1)
            out[layer, i] = miss.max()
    return out


def numbers_of(gaps, states) -> dict:
    """``gaps``: the requests' token gaps; ``states``: :func:`state_gaps`
    of the requests whose state was read, None for one that left none. The
    state's numbers are the worst request's: over every layer, and in the
    first layer alone."""
    gaps = np.concatenate(gaps)
    out = {"logit_gap_max": float(gaps.max()),
           "logit_gap_mean": float(gaps.mean())}
    whole = bool(states) and all(s is not None for s in states)
    worst = np.max(states, axis=0) if whole else None        # (layers, 2)
    for i, name in enumerate(("state_s_gap", "state_z_gap")):
        out[name] = float(worst[:, i].max()) if whole else None
        out[name + "_first"] = float(worst[0, i]) if whole else None
    return out


def by_layer(states):
    """S's and Z's miss a layer, the worst request's: printed, judges
    nothing."""
    if not states or any(s is None for s in states):
        return None
    worst = np.max(states, axis=0)
    return {"s": [float(x) for x in worst[:, 0]],
            "z": [float(x) for x in worst[:, 1]]}


def serving_gaps(get, m, sample, served, controls=(), chunk=128):
    """(program's numbers, {control name: numbers}, tokens, the state's
    miss a layer of each). A control does
    not decode: at each row it is the token the control puts first, judged
    by the same reference logits, and its state is what its form of the
    reference holds over the same tokens. ``served``: what each request
    left in its slot (``program_retention.served_states``)."""
    mine, mine_s, tokens = [], [], 0
    theirs = {name: ([], []) for name in controls}
    served = list(served or [None] * STATE_REQUESTS)
    for i, item in enumerate(sample):
        read = i < len(served)       # a request whose state is compared
        left = served[i] if read else None
        logits, states = request_logits(get, m, item)
        mine.append(token_gaps(logits, item["tokens"]))
        tokens += len(item["tokens"])
        if read and left is None:
            mine_s.append(None)
        elif read:
            want = states
            if not np.array_equal(left["tokens"], item["tokens"]):
                # served in other company, a bfloat16 tie fell the other
                # way: the state is judged over the tokens it was fed
                _, want = request_logits(get, m, dict(item,
                                                      tokens=left["tokens"]))
            mine_s.append(state_gaps(left["states"], want))
        for name in controls:
            form = faults(chunk).get(name)
            low, low_states = (request_logits(get, m, item, **form) if form
                               else request_logits(get, m, item, mm=name))
            theirs[name][0].append(token_gaps(logits, low.argmax(-1)))
            if read:
                theirs[name][1].append(state_gaps(low_states, states))
    layers = {"program": by_layer(mine_s),
              **{name: by_layer(g[1]) for name, g in theirs.items()}}
    return (numbers_of(mine, mine_s),
            {name: numbers_of(*g) for name, g in theirs.items()}, tokens,
            layers)


def serving_checks(config, seed, sample, limits, control_mm=None):
    m = program_retention.model_section(config)
    # first, while the engine is there: it is let go inside
    served = program_retention.served_states(sample[:STATE_REQUESTS])
    if not sample:
        return compare.checks_of(dict.fromkeys(
            ("logit_gap_max", "logit_gap_mean", "state_s_gap",
             "state_s_gap_first", "state_z_gap", "state_z_gap_first")),
            limits), {"tokens": 0, "requests": 0}
    get = W.provider(m, seed, jnp.dtype(config["deployment"]["dtype"]))
    # "fp8", or "fp8+state_dropped+bf16_state" for the faults beside it
    controls = tuple(c for c in (control_mm or "").split("+") if c)
    chunk = int(config["deployment"]["engine"]["prompt_buckets"][-1])
    for name in controls:
        if name not in faults(chunk) and name not in ref.MATMULS:
            raise ValueError(f"unknown control {name!r}")
    numbers, theirs, tokens, layers = serving_gaps(
        get, m, sample, served, controls, chunk)
    detail = {"tokens": tokens, "requests": len(sample),
              "longest": max(len(i["prompt"]) + len(i["tokens"])
                             for i in sample), "numbers": numbers,
              "state_gap_by_layer": layers,
              "served_again_alike": None if served is None else sum(
                  left is not None and np.array_equal(left["tokens"],
                                                      item["tokens"])
                  for item, left in zip(sample, served))}
    if controls:             # only the control tool asks for it
        detail["control"] = theirs
    return compare.checks_of(numbers, limits), detail
