"""``compare.py``'s twin for the ``nemotron_h`` hybrid model: the chat cell's
rule (a seeded sample of the requests the window finished, the longest in it;
the plain reference's logits at the rows that produced the served tokens)
against ``reference/nemotron_h_plain.py``, whose weights come a layer at a time
from ``weights_nemotron_h.provider``. Prefill, chunks and then decode through
state AND pages are so held to the reference's full forward, its Mamba-2
layers as the token-by-token recurrence.

From the logits: the mean and the 99th percentile of a served token's logit
below the reference's best are compared and the widest is printed (a router's
choice flips where two scores lie within bfloat16 rounding, and one flipped
token moves as far as a fault: ``PERF.md`` §6, PR 27). The logits are
dominated by the bfloat16 projections and cannot see the precision of the
state itself, so four numbers read the STATE: the engine the window ran on
serves the sample's longest request and one more again
(``program_nemotron_h.served_states``) and what each leaves in its slot is
compared with the reference's float32 recurrence over the same tokens, as the
relative miss (Frobenius): ``state_s_gap_first``, the worst head of the first
Mamba-2 layer (its input is the embedding, the same on both sides, so the miss
there is the layer's own bfloat16 projections and the state's arithmetic), and
``state_s_gap``, the worst layer's state as a whole (deeper layers add the
drift of the bfloat16 hidden states and the flipped router choices behind
them; a head that remembers a token or two is all but its last inputs, so the
worst head of a deep layer reads near 1 on a sound run and is only printed);
``state_conv_gap_first`` / ``state_conv_gap`` for the convolution's carried
inputs.

The control tool asks besides for the same numbers of the reference computed
in float8 (``control_mm``), of the planted fault "what a Mamba-2 layer
carries dropped at every chunk boundary" (``state_dropped``) and of a state
KEPT in bfloat16 (``bf16_state``: rounded at every chunk boundary of the
prompt and at every decode step, where an engine would write it).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmark import compare, program_nemotron_h
from benchmark import weights_nemotron_h as W
from benchmark.compare_retention import token_gaps
from benchmark.reference import nemotron_h_plain as ref

pick_sample = compare.pick_sample
# requests whose state is read back: the sample's first is its longest
STATE_REQUESTS = 2
# reference sequences are padded to a multiple: the mix's longest request is
# 3-4 k tokens, so a run compiles the reference's three kinds of layer at
# most four times each
PAD = 1024
NAMES = ("logit_gap_max", "logit_gap_mean", "logit_gap_p99", "state_s_gap",
         "state_s_gap_first", "state_conv_gap", "state_conv_gap_first")


def faults(chunk: int) -> dict:
    """The planted forms of the reference, by name; ``chunk`` is the
    engine's prompt chunk, where the program carries the state."""
    return {"state_dropped": {"window": chunk},
            "bf16_state": {"chunk": chunk, "state_dtype": "bfloat16"}}


def request_logits(get, m, item, mm="f32", **form):
    """Reference logits at the rows that produced ``item``'s tokens, and
    what each Mamba-2 layer holds once the last but one of them is fed (the
    last is never fed): ``(logits, [(S, carried inputs) a layer])``."""
    prompt, toks = item["prompt"], item["tokens"]
    ids = np.concatenate([prompt, toks]).astype(np.int32)
    n = -(-len(ids) // PAD) * PAD
    ids = np.concatenate([ids, np.zeros(n - len(ids), np.int32)])
    rows = len(prompt) - 1 + np.arange(len(toks))
    logits, states = ref.forward_logits(
        get, m, ids, rows, mm, experts_held=m["experts_held"],
        state_at=len(prompt) + len(toks) - 1, prefill=len(prompt), **form)
    return np.asarray(logits), states


def state_gaps(got, want):
    """(layers, 3) relative misses (Frobenius) of each Mamba-2 layer: its
    worst head of ``S`` (H, P, N), ``S`` as a whole, and its carried inputs
    as a whole. A head that remembers a token or two holds little else than
    its last inputs, so one flipped router choice behind it moves the worst
    head by its whole norm; the whole layer is weighed by the heads that
    remember hundreds."""
    out = np.zeros((len(want), 3))
    for layer, (pair, ref_pair) in enumerate(zip(got, want)):
        g, w = (np.asarray(a, np.float32) for a in (pair[0], ref_pair[0]))
        heads = g.shape[0]
        out[layer, 0] = (
            np.linalg.norm((g - w).reshape(heads, -1), axis=1)
            / np.linalg.norm(w.reshape(heads, -1), axis=1)).max()
        out[layer, 1] = np.linalg.norm(g - w) / np.linalg.norm(w)
        g, w = (np.asarray(a, np.float32) for a in (pair[1], ref_pair[1]))
        out[layer, 2] = np.linalg.norm(g - w) / np.linalg.norm(w)
    return out


def head_gaps(got, want):
    """The first Mamba-2 layer's relative miss a HEAD of ``S`` and the
    reference's norm a head, each (H,): printed, so that a steadier guard
    than the worst head can be chosen from readings (``PERF.md`` §7)."""
    g, w = (np.asarray(a[0][0], np.float32) for a in (got, want))
    heads = g.shape[0]
    norm = np.linalg.norm(w.reshape(heads, -1), axis=1)
    return np.linalg.norm((g - w).reshape(heads, -1), axis=1) / norm, norm


def head_memory(get, m):
    """Tokens a head of the first Mamba-2 layer remembers, at its bias:
    ``1 / (softplus(dt_bias) exp(A_log))``, (H,)."""
    i = m["hybrid_override_pattern"].index("M")
    dt = np.logaddexp(0.0, np.asarray(
        get(f"model.layers.{i}.mixer.dt_bias"), np.float64))
    return 1.0 / (dt * np.exp(np.asarray(
        get(f"model.layers.{i}.mixer.A_log"), np.float64)))


def numbers_of(gaps, states) -> dict:
    """``gaps``: the requests' token gaps; ``states``: :func:`state_gaps`
    of the requests whose state was read, None for one that left none. The
    state's numbers are the worst request's: in the first Mamba-2 layer the
    worst head (``state_s_gap_first``) and the carried inputs
    (``state_conv_gap_first``), over every layer each as a whole
    (``state_s_gap``, ``state_conv_gap``)."""
    gaps = np.concatenate(gaps)
    out = {"logit_gap_max": float(gaps.max()),
           "logit_gap_mean": float(gaps.mean()),
           "logit_gap_p99": float(np.percentile(gaps, 99))}
    whole = bool(states) and all(s is not None for s in states)
    worst = np.max(states, axis=0) if whole else None        # (layers, 3)
    out["state_s_gap"] = float(worst[:, 1].max()) if whole else None
    out["state_s_gap_first"] = float(worst[0, 0]) if whole else None
    out["state_conv_gap"] = float(worst[:, 2].max()) if whole else None
    out["state_conv_gap_first"] = float(worst[0, 2]) if whole else None
    return out


def by_layer(states):
    """The three misses a Mamba-2 layer, the worst request's: printed,
    judges nothing."""
    if not states or any(s is None for s in states):
        return None
    worst = np.max(states, axis=0)
    return {"s_worst_head": [float(x) for x in worst[:, 0]],
            "s": [float(x) for x in worst[:, 1]],
            "conv": [float(x) for x in worst[:, 2]]}


def serving_gaps(get, m, sample, served, controls=(), chunk=128):
    """(program's numbers, {control name: numbers}, tokens, the state's
    miss a layer of each). A control does not decode: at each row it is the
    token the control puts first, judged by the same reference logits, and
    its state is what its form of the reference holds over the same tokens.
    ``served``: what each request left in its slot
    (``program_nemotron_h.served_states``)."""
    mine, mine_s, tokens = [], [], 0
    theirs = {name: ([], []) for name in controls}
    heads = {name: [] for name in ("program", "norm", *controls)}
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
            gap, norm = head_gaps(left["states"], want)
            heads["program"].append(gap.tolist())
            heads["norm"].append(norm.tolist())
        for name in controls:
            form = faults(chunk).get(name)
            low, low_states = (request_logits(get, m, item, **form) if form
                               else request_logits(get, m, item, mm=name))
            theirs[name][0].append(token_gaps(logits, low.argmax(-1)))
            if read:
                theirs[name][1].append(state_gaps(low_states, states))
                heads[name].append(head_gaps(low_states, states)[0].tolist())
    layers = {"program": by_layer(mine_s),
              **{name: by_layer(g[1]) for name, g in theirs.items()}}
    if heads["program"]:
        layers["first_layer_heads"] = dict(
            heads, memory_tokens=head_memory(get, m).tolist())
    return (numbers_of(mine, mine_s),
            {name: numbers_of(*g) for name, g in theirs.items()}, tokens,
            layers)


def serving_checks(config, seed, sample, limits, control_mm=None):
    m = program_nemotron_h.model_section(config)
    # first, while the engine is there: it is let go inside
    served = program_nemotron_h.served_states(sample[:STATE_REQUESTS])
    if not sample:
        return compare.checks_of(dict.fromkeys(NAMES), limits), \
            {"tokens": 0, "requests": 0}
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
