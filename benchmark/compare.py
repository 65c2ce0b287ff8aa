"""The comparison that decides ``correct``: the timed path's own products
against the plain reference, each number beside a limit of its own.

Serving: a seeded sample of the requests the window finished, the longest in
it; the reference runs once over each prompt with its served tokens, and the
number is the widest gap by which a served token's logit lies below the
reference's best (greedy traffic). Training: the losses of the first three
steps (each printed; the widest of their gaps compared), the norm of the first
gradient as the optimizer got it and the norm of the parameters' change after
the three, both by the worst leaf. The limits
come from ``benchmark/limits/<cell>.json`` and were set from measured
readings (``PERF.md`` lists them); a number with no limit fails.
"""
from __future__ import annotations

import importlib
from statistics import median

import jax.numpy as jnp
import numpy as np

from benchmark import weights as W
from benchmark.reference import llama_plain as ref

PAD = 256      # reference sequences are padded to a multiple: few programs


def pick_sample(stamps, requests, t0, t1, k, seed):
    """``k`` requests that finished ``ok`` inside the window, drawn from the
    seed, the longest (prompt + served tokens) among them."""
    by_rid = {r["rid"]: r for r in requests}
    done = [st for st in stamps if st.status == "ok" and st.seen
            and st.seen[-1][0] < t1 and st.first is not None
            and st.first >= t0 and st.n_final]
    if not done:
        return []
    done.sort(key=lambda st: st.rid)
    longest = max(done, key=lambda st: (st.prompt_len + st.n_final, -st.rid))
    rest = [st for st in done if st is not longest]
    rng = np.random.default_rng(int(seed))
    picks = [longest] + [rest[i] for i in
                         rng.permutation(len(rest))[:max(k - 1, 0)]]
    return [{"rid": st.rid, "prompt": by_rid[st.rid]["prompt"],
             "tokens": np.asarray(st.tokens, np.int32)} for st in picks]


def request_logits(weights, m, item, mm="f32"):
    """Reference logits at the rows that produced ``item``'s tokens."""
    prompt, toks = item["prompt"], item["tokens"]
    ids = np.concatenate([prompt, toks]).astype(np.int32)
    n = -(-len(ids) // PAD) * PAD
    ids = np.concatenate([ids, np.zeros(n - len(ids), np.int32)])
    rows = len(prompt) - 1 + np.arange(len(toks))
    return np.asarray(ref.forward_logits(weights, m, ids, rows, mm))


def served_gap(logits, toks) -> float:
    """Widest gap of a served token's logit below the reference's best."""
    return float(np.max(logits.max(-1) - logits[np.arange(len(toks)), toks]))


def serving_gaps(weights, m, sample, control_mm=None):
    """(program's widest gap, control's widest gap or None) over a sample.
    The control does not decode: at each row it is the token the lower
    precision puts first, judged by the same reference logits."""
    gap, c_gap, tokens = 0.0, (0.0 if control_mm else None), 0
    for item in sample:
        logits = request_logits(weights, m, item)
        gap = max(gap, served_gap(logits, item["tokens"]))
        tokens += len(item["tokens"])
        if control_mm:
            low = request_logits(weights, m, item, control_mm)
            c_gap = max(c_gap, served_gap(logits, low.argmax(-1)))
    return gap, c_gap, tokens


def serving_checks(config, seed, sample, limits, control_mm=None):
    m = config["model"]
    if not sample:
        return checks_of({"logit_gap_max": None}, limits), \
            {"tokens": 0, "requests": 0}
    weights = W.make_weights(m, seed, jnp.dtype(config["deployment"]["dtype"]))
    gap, c_gap, tokens = serving_gaps(weights, m, sample, control_mm)
    detail = {"tokens": tokens, "requests": len(sample),
              "longest": max(len(i["prompt"]) + len(i["tokens"])
                             for i in sample)}
    if control_mm:           # only the control tool asks for it
        detail["control"] = {control_mm: {"logit_gap_max": c_gap}}
    return checks_of({"logit_gap_max": gap}, limits), detail


def _limit(limits, name):
    limit = (limits.get(name) or {}).get("limit")
    return None if limit is None else float(limit)


# ----------------------------------------------------------------- training

def worst_leaf_gap(got: dict, want: dict, keep=None, floor=True) -> float:
    """Largest |got - want| over leaves, against the reference's norm of that
    leaf or of the median leaf, whichever is larger: the builder's contract's
    measure, since some gradients are all but zero. ``floor`` False is the
    plain measure (that leaf's own norm alone), printed beside it."""
    med = median(want.values()) if floor else 0.0
    return max(abs(got[k] - want[k]) / max(want[k], med)
               for k in want if keep is None or k in keep)


def moving_leaves(ref_grad_norms: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's. The others move under Adam by round-off
    alone and are left out of the change."""
    med = median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v >= 1e-3 * med}


def reference_training(config, traffic, seed, mm="f32", half_batch=False):
    """Losses of the first steps, first-gradient norms and change norms of
    the plain reference on the seed's own first batches."""
    m = config["model"]
    gen = importlib.import_module(traffic["generator"])
    steps = int(traffic["warm_steps"])
    batches = gen.batches(traffic, seed, m["vocab_size"], steps)
    dtype = jnp.dtype(config["deployment"]["dtype"])
    trainer = ref.PlainTrainer(W.make_weights(m, seed, dtype), m,
                               config["deployment"]["train"], mm=mm,
                               half_batch=half_batch)
    losses = [trainer.step(b) for b in batches]
    return {"losses": losses, "grad_norms": trainer.grad_norms,
            "change_norms": trainer.change_norms(
                W.make_weights(m, seed, dtype))}


def training_numbers(observed: dict, want: dict) -> dict:
    keep = moving_leaves(want["grad_norms"])
    out = {f"loss{i + 1}_gap": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(observed["losses"],
                                          want["losses"]))}
    # one step's gap is rounding with a random sign, so a lower precision
    # reads small on it by chance; the widest of the steps' gaps separates
    out["loss_gap_max"] = max(out.values())
    out["grad_norm_gap"] = worst_leaf_gap(observed["grad_norms"],
                                          want["grad_norms"])
    out["change_norm_gap"] = worst_leaf_gap(observed["change_norms"],
                                            want["change_norms"], keep)
    return out


def plain_leaf_gaps(observed: dict, want: dict) -> dict:
    """The two worst-leaf numbers without the median leaf's floor; printed,
    never compared."""
    keep = moving_leaves(want["grad_norms"])
    return {"grad_norm_gap": worst_leaf_gap(observed["grad_norms"],
                                            want["grad_norms"], floor=False),
            "change_norm_gap": worst_leaf_gap(observed["change_norms"],
                                              want["change_norms"], keep,
                                              floor=False)}


def checks_of(numbers: dict, limits: dict) -> list:
    """``(name, value, limit)`` for every number but those the limits file
    names as not compared (a number with no upper reading: it could only
    fail sound runs; ``PERF.md`` names each with its readings). A number
    with no entry at all has no limit and fails."""
    return [(name, (float(v) if v is not None and np.isfinite(v) else None),
             _limit(limits, name)) for name, v in numbers.items()
            if not (limits.get(name) or {}).get("not_compared")]


def training_checks(config, traffic, seed, observed, limits,
                    control_mm=None):
    want = reference_training(config, traffic, seed)
    numbers = training_numbers(observed, want)
    control = {}
    if control_mm:           # only the control tool asks for it
        low = reference_training(config, traffic, seed, mm=control_mm)
        control[control_mm] = training_numbers(low, want)
        half = reference_training(config, traffic, seed, half_batch=True)
        control["half_batch"] = training_numbers(half, want)
    return checks_of(numbers, limits), {
        "reference_losses": want["losses"],
        "program_losses": observed["losses"],
        "numbers": numbers, "plain_leaf_gaps": plain_leaf_gaps(observed, want),
        "control": control,
        "leaves_left_out": len(want["grad_norms"])
        - len(moving_leaves(want["grad_norms"]))}
