"""``compare.py``'s twin for the sparse-expert / latent-attention model: the
same comparison (a seeded sample of the requests the window finished, the
plain reference's logits at the rows that produced the served tokens, the
widest gap of a served token's logit below the reference's best) against
``reference/moe_mla_plain.py``, whose weights come a layer at a time from
``weights_moe_mla.provider``.

The control tool asks besides for the same numbers of the reference computed
in float8 (``control_mm``) and of the planted fault "one expert's output left
out", and, on the sample's longest request, for how often a bfloat16 hidden
state changes a router's choice (``route_flips``): the hazard ISSUE 27 names.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, program_moe_mla
from benchmark import weights_moe_mla as W
from benchmark.reference import moe_mla_plain as ref

pick_sample = compare.pick_sample
# reference sequences are padded to a multiple: the mix's longest request
# is 1.7-2.0 k tokens, so a run compiles the reference's layers once or twice
PAD = 512
FAULT = "expert_left_out"


def request_logits(get, m, item, mm="f32", drop_expert=None):
    """Reference logits at the rows that produced ``item``'s tokens."""
    prompt, toks = item["prompt"], item["tokens"]
    ids = np.concatenate([prompt, toks]).astype(np.int32)
    n = -(-len(ids) // PAD) * PAD
    ids = np.concatenate([ids, np.zeros(n - len(ids), np.int32)])
    rows = len(prompt) - 1 + np.arange(len(toks))
    return np.asarray(ref.forward_logits(
        get, m, ids, rows, mm, experts_held=m["experts_held"],
        drop_expert=drop_expert))


def busiest_expert(get, m, item, layer):
    """The expert of ``layer`` that the request's tokens choose most: the
    one the planted fault leaves out."""
    ids = np.concatenate([item["prompt"], item["tokens"]]).astype(np.int32)
    picks = route_choices(get, m, ids, bf16_hidden=False)[layer]
    return int(np.bincount(picks.reshape(-1),
                           minlength=m["n_routed_experts"]).argmax())


def route_choices(get, m, ids, bf16_hidden):
    """{sparse layer: (S, top_k) chosen experts} of the reference's forward
    over ``ids``; with ``bf16_hidden`` every router sees its input rounded
    to bfloat16 (the hidden state itself stays float32)."""
    x = get("model.embed_tokens.weight")[jnp.asarray(ids)].astype(ref.F32)
    frozen = ref._freeze(m)
    out = {}
    for i in range(m["num_hidden_layers"]):
        lw = {leaf: get(f"model.layers.{i}.{leaf}")
              for leaf in ref.layer_leaf_names(m, i)}
        sparse = i >= m["first_k_dense_replace"]
        if sparse:
            out[i] = np.asarray(_router_input_choices(
                x, lw, m=frozen, bf16_hidden=bf16_hidden))
        x = ref._layer_jit(x, lw, jnp.int32(-1), m=frozen, mm="f32",
                           sparse=sparse, experts_held=m["experts_held"])
    return out


@partial(jax.jit, static_argnames=("m", "bf16_hidden"))
def _router_input_choices(x, lw, *, m, bf16_hidden):
    m = dict(m)
    eps = m["rms_norm_eps"]
    y = x + ref.attention(ref.rms_norm(x, lw["input_layernorm.weight"], eps),
                          lw, m, ref.mm_f32)
    h = ref.rms_norm(y, lw["post_attention_layernorm.weight"], eps)
    if bf16_hidden:
        h = h.astype(jnp.bfloat16).astype(ref.F32)
    ids, _ = ref.router(h, lw, m, ref.mm_f32)
    return jnp.sort(ids, axis=-1)


def route_flips(get, m, item):
    """Share of (token, sparse layer) pairs whose chosen SET changes when
    the router's input is rounded to bfloat16."""
    ids = np.concatenate([item["prompt"], item["tokens"]]).astype(np.int32)
    exact = route_choices(get, m, ids, bf16_hidden=False)
    low = route_choices(get, m, ids, bf16_hidden=True)
    changed = sum(int((exact[i] != low[i]).any(-1).sum()) for i in exact)
    return changed / float(len(ids) * len(exact))


def token_gaps(logits, toks):
    """Each served token's gap below the reference's best at its row."""
    return logits.max(-1) - logits[np.arange(len(toks)), toks]


def gap_numbers(gaps) -> dict:
    """The numbers of a sample's per-token gaps: the widest
    (``compare.py``'s number), the mean and the 99th percentile. A router
    that flips a choice where two scores lie within bfloat16 rounding moves
    a few tokens' logits as far as a fault would, so the widest gap of a
    sound run reaches the control's; the mean does not (PERF.md, PR 27)."""
    gaps = np.concatenate(gaps)
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean()),
            "logit_gap_p99": float(np.percentile(gaps, 99))}


def serving_gaps(get, m, sample, control_mm=None, fault=None):
    """(program's numbers, {control name: numbers}, tokens). A control
    does not decode: at each row it is the token the control puts first,
    judged by the same reference logits."""
    mine, tokens = [], 0
    controls = {name: [] for name in (control_mm, fault) if name}
    for item in sample:
        logits = request_logits(get, m, item)
        mine.append(token_gaps(logits, item["tokens"]))
        tokens += len(item["tokens"])
        if control_mm:
            low = request_logits(get, m, item, control_mm)
            controls[control_mm].append(token_gaps(logits, low.argmax(-1)))
        if fault:
            layer = m["first_k_dense_replace"]
            drop = (layer, busiest_expert(get, m, item, layer))
            bad = request_logits(get, m, item, drop_expert=drop)
            controls[fault].append(token_gaps(logits, bad.argmax(-1)))
    return (gap_numbers(mine),
            {name: gap_numbers(g) for name, g in controls.items()}, tokens)


def serving_checks(config, seed, sample, limits, control_mm=None):
    m = program_moe_mla.model_section(config)
    if not sample:
        return compare.checks_of(
            {"logit_gap_max": None, "logit_gap_mean": None,
             "logit_gap_p99": None}, limits), {"tokens": 0, "requests": 0}
    get = W.provider(m, seed, jnp.dtype(config["deployment"]["dtype"]))
    # "fp8", or "fp8+expert_left_out" for the planted fault beside it
    control_mm, _, fault = (control_mm or "").partition("+")
    if fault and fault != FAULT:
        raise ValueError(f"unknown planted fault {fault!r}")
    numbers, controls, tokens = serving_gaps(get, m, sample,
                                             control_mm or None,
                                             fault or None)
    detail = {"tokens": tokens, "requests": len(sample),
              "longest": max(len(i["prompt"]) + len(i["tokens"])
                             for i in sample), "numbers": numbers}
    if control_mm:           # only the control tool asks for it
        detail["control"] = controls
        detail["route_flip_share_bf16_hidden"] = route_flips(get, m,
                                                             sample[0])
    return compare.checks_of(numbers, limits), detail
