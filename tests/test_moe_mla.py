"""Sparse experts + latent attention on the serving path (ISSUE 27).

Everything is float32 on the CPU at a tiny size (hidden 64, 4 heads, latent
32, 16 experts top-4, 3 layers of which the first is dense) on seeded
weights, against the plain reference's FULL forward
(``paddle_tpu/models/reference/moe_mla_plain.py``: plain attention form, loop
over experts, no cache). Logits are compared, not sampled tokens.

Tolerances. ``LOGIT_TOL`` 2e-4: both sides are float32 (the reference at
``highest``), logits are of order 1, and what separates them is the order of
float32 sums (absorbed against plain form, grouped against looped experts,
online against whole softmax): measured under 2e-5 here. The same model with
its weights rounded to bfloat16 misses by over 1e-3
(``test_bf16_weights_would_fail_the_tolerance``), so a lower precision
anywhere on the path fails. ``GAP_TOL`` 2e-4 is the same bound on the
engine's products, where only tokens come out: the widest gap of a served
(greedy) token's reference logit below the reference's best.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import telemetry
from paddle_tpu.models import (
    ContinuousBatchingEngine,
    ServingFrontend,
    TPShardedEngine,
    generate,
    moe_mla_tiny_config,
)
from paddle_tpu.models.moe_mla import SparseExperts, route
from paddle_tpu.models.reference import moe_mla_plain as ref

from _tiny_models import sparse_latent_model

LOGIT_TOL = 2e-4
GAP_TOL = 2e-4
VOCAB = 128


def _m(cfg):
    """The reference's view of a config: the published keys."""
    keys = ("vocab_size hidden_size intermediate_size moe_intermediate_size "
            "num_hidden_layers num_attention_heads q_lora_rank kv_lora_rank "
            "qk_nope_head_dim qk_rope_head_dim v_head_dim n_routed_experts "
            "num_experts_per_tok n_shared_experts first_k_dense_replace "
            "routed_scaling_factor norm_topk_prob rms_norm_eps rope_theta")
    return {k: getattr(cfg, k) for k in keys.split()}


def _weights(model):
    return {k: p._value for k, p in model.named_parameters()}


@pytest.fixture(scope="module")
def model():
    return sparse_latent_model()


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _ref_logits(model, ids, rows=None, weights=None):
    """Reference logits at ``rows``; ids padded to one length (causal
    attention keeps the padding out), so the reference compiles once."""
    rows = np.arange(len(ids)) if rows is None else rows
    padded = np.zeros(64, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(ref.forward_logits(
        weights or _weights(model), _m(model.config), padded, rows))


def test_the_two_copies_of_the_reference_are_one_file():
    import benchmark.reference.moe_mla_plain as bench_copy

    with open(ref.__file__) as a, open(bench_copy.__file__) as b:
        assert a.read() == b.read()


def test_uncached_forward_matches_the_reference(model):
    ids = _ids(24)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, _ref_logits(model, ids), atol=LOGIT_TOL,
                               rtol=0)


def test_bf16_weights_would_fail_the_tolerance(model):
    """The tolerance is tight: the reference with every weight rounded to
    bfloat16 (float32 arithmetic) already misses it."""
    ids = _ids(24)
    low = {k: v.astype(jnp.bfloat16).astype(v.dtype)
           for k, v in _weights(model).items()}
    got = _ref_logits(model, ids, weights=low)
    assert np.abs(got - _ref_logits(model, ids)).max() > 5 * LOGIT_TOL


def _cached_forward(model, make):
    """``step(tokens, ks, vs, length, *extra)`` -> (logits, ks, vs), jitted
    once a shape as the engine's programs are (an eager call would run
    every op, and the interpreted kernels, one dispatch at a time).
    ``make`` builds one layer's cache from its two buffers; a ``length``
    of None is the static 0 of a fresh prefill."""
    nl = model.config.num_hidden_layers

    def step(tokens, ks, vs, length, *extra):
        length = 0 if length is None else length
        caches = [make(ks[i], vs[i], length, *extra) for i in range(nl)]
        logits, out = model(paddle.to_tensor(tokens), caches=caches)
        paged = hasattr(out[0], "k_pages")
        return (logits._value,
                [c.k_pages if paged else c.k for c in out],
                [c.v_pages if paged else c.v for c in out])

    return jax.jit(step)


def test_absorbed_form_equals_plain_form(model):
    """A fresh prefill (plain form) of 8 tokens, then 8 more as one chunk
    and one more alone through a contiguous cache (absorbed form): every
    logit equals the plain reference's full forward."""
    from paddle_tpu.models.generation import _make_static_cache

    nl = model.config.num_hidden_layers
    k_shape, v_shape = model.kv_page_shapes()
    ks = [jnp.zeros((1, 32) + k_shape) for _ in range(nl)]
    vs = [jnp.zeros((1, 32) + v_shape) for _ in range(nl)]
    step = _cached_forward(model, _make_static_cache)
    ids = _ids(17, seed=1)
    got = []
    for lo, hi in [(0, 8), (8, 16), (16, 17)]:
        logits, ks, vs = step(ids[None, lo:hi], ks, vs,
                              jnp.int32(lo) if lo else None)
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.concatenate(got), _ref_logits(model, ids),
                               atol=LOGIT_TOL, rtol=0)


def test_paged_per_slot_decode_matches_the_reference(model):
    """The engine's cache layout by hand: scrambled block tables, per-slot
    lengths, a chunk at page-aligned per-row bases (masked composition)
    and single-token steps (the paged latent kernel, interpreted), two
    rows at different depths, against the full forward on logits."""
    from paddle_tpu.models.generation import _make_paged_cache

    nl, page = model.config.num_hidden_layers, 8
    k_shape, v_shape = model.kv_page_shapes()
    ks = [jnp.zeros((9, page) + k_shape) for _ in range(nl)]
    vs = [jnp.zeros((9, page) + v_shape) for _ in range(nl)]
    tables = jnp.asarray([[5, 2, 7, 0], [1, 6, 3, 4]], jnp.int32)
    rows = [_ids(18, seed=2), _ids(10, seed=3)]

    def make(k, v, length, aligned):
        return _make_paged_cache(k, v, tables, page, length,
                                 aligned_bases=aligned, attn_pages=4)

    aligned = _cached_forward(model, lambda k, v, n: make(k, v, n, True))
    ragged = _cached_forward(model, lambda k, v, n: make(k, v, n, False))
    # both rows' first 8 tokens (fresh, plain form), then row 0's next 8
    # as a chunk at base 8 beside a rewrite of row 1's page at base 0
    fresh, ks, vs = aligned(np.stack([rows[0][:8], rows[1][:8]]), ks, vs,
                            None)
    chunk, ks, vs = aligned(np.stack([rows[0][8:16], rows[1][:8]]), ks, vs,
                            jnp.asarray([8, 0], jnp.int32))
    want0 = _ref_logits(model, rows[0])
    want1 = _ref_logits(model, rows[1])
    np.testing.assert_allclose(fresh[0], want0[:8], atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(fresh[1], want1[:8], atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(chunk[0], want0[8:16], atol=LOGIT_TOL, rtol=0)
    for t in range(2):
        tok = np.stack([rows[0][16 + t:17 + t], rows[1][8 + t:9 + t]])
        one, ks, vs = ragged(tok, ks, vs,
                             jnp.asarray([16 + t, 8 + t], jnp.int32))
        np.testing.assert_allclose(one[0, 0], want0[16 + t], atol=LOGIT_TOL,
                                   rtol=0)
        np.testing.assert_allclose(one[1, 0], want1[8 + t], atol=LOGIT_TOL,
                                   rtol=0)


# ------------------------------------------------------------- the router


def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    """One token, four experts, top-2. Without the bias experts 0 and 1
    win; a bias on expert 3 changes the CHOICE to {0, 3}, and the weights
    are the sigmoid scores of 0 and 3 alone, normalised and scaled: the
    bias is in no weight."""
    x = jnp.asarray([[1.0, 0.0]])
    gate = jnp.asarray([[2.0, 1.0, 0.0, 0.5], [0.0] * 4])
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0, 0.0, 0.5]))
    ids, w = route(x, gate, jnp.zeros(4), 2, 2.5)
    assert sorted(np.asarray(ids[0])) == [0, 1]
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.2])          # lifts 3 over 1
    ids, w = route(x, gate, bias, 2, 2.5)
    order = np.argsort(np.asarray(ids[0]))
    assert list(np.asarray(ids[0])[order]) == [0, 3]
    want = 2.5 * np.asarray([s[0], s[3]]) / float(s[0] + s[3])
    np.testing.assert_allclose(np.asarray(w[0])[order], want, rtol=1e-6)


def test_the_models_bias_changes_choices(model):
    """The fixture's correction bias is no decoration: on seeded inputs it
    changes at least one choice against a zero bias."""
    layer = model.model.layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 64))
    args = (x, layer.gate.weight._value)
    with_b, _ = route(*args, layer.e_score_correction_bias._value, 4, 2.5)
    without, _ = route(*args, jnp.zeros(16), 4, 2.5)
    assert (np.sort(np.asarray(with_b), 1)
            != np.sort(np.asarray(without), 1)).any()


# ------------------------------------------------------------- the shares


@pytest.mark.parametrize("count", [16, 8, 4])
def test_shares_add_up_to_the_uncut_layer(model, count):
    """The guide's shares test: the routed parts that every
    ``experts_held`` range gives, with the shared expert counted once, add
    up to the uncut reference's layer output."""
    cfg = model.config
    src = model.model.layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 24, cfg.hidden_size))
    m = _m(cfg)
    lw = {k[len("model.layers.1."):]: v for k, v in _weights(model).items()
          if k.startswith("model.layers.1.")}
    want = (ref.routed_experts(x[0], lw, m, ref.mm_f32)
            + ref.swiglu(x[0], lw["mlp.shared_experts.gate_proj.weight"],
                         lw["mlp.shared_experts.up_proj.weight"],
                         lw["mlp.shared_experts.down_proj.weight"],
                         ref.mm_f32))
    shared = np.asarray(src.shared_experts(paddle.to_tensor(x))._value[0])
    total = np.zeros_like(shared)
    for first in range(0, cfg.n_routed_experts, count):
        part_cfg = moe_mla_tiny_config(experts_held=(first, count))
        part = SparseExperts(part_cfg)
        part.gate.weight._value = src.gate.weight._value
        part.e_score_correction_bias._value = \
            src.e_score_correction_bias._value
        part.experts_gate_up._value = \
            src.experts_gate_up._value[first:first + count]
        part.experts_down._value = \
            src.experts_down._value[first:first + count]
        for a, b in zip(part.shared_experts.parameters(),
                        src.shared_experts.parameters()):
            a._value = b._value
        y, _ = part(paddle.to_tensor(x))
        total += np.asarray(y._value[0]) - shared    # the routed part alone
        # and the reference, given the same share, gives the same part
        held = dict(lw)
        for leaf in ("mlp.experts_gate_up", "mlp.experts_down"):
            held[leaf] = lw[leaf][first:first + count]
        np.testing.assert_allclose(
            np.asarray(y._value[0]) - shared,
            np.asarray(ref.routed_experts(x[0], held, m, ref.mm_f32,
                                          experts_held=(first, count))),
            atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(total + shared, np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


def test_dead_rows_are_assigned_to_nobody(model):
    """Rows the engine marks dead cost no expert and count nowhere."""
    layer = model.model.layers[1].mlp
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 64))
    live = jnp.asarray([True, False, True, False])
    y, stats = layer(paddle.to_tensor(x), live=live)
    y_all, stats_all = layer(paddle.to_tensor(x))
    assert int(stats[0]) == 2 * 4 and int(stats_all[0]) == 4 * 4
    assert int(stats[1]) <= 8 and int(stats[3]) == 1
    np.testing.assert_allclose(np.asarray(y._value)[[0, 2]],
                               np.asarray(y_all._value)[[0, 2]], atol=1e-6)


# -------------------------------------------------------------- the engine


def _engine(model, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prompt_buckets", (8, 16))
    kw.setdefault("pool_pages", 24)
    return ContinuousBatchingEngine(model, **kw)


def _served_gap(model, prompt, tokens):
    """Widest gap of a served token's reference logit below the best."""
    ids = np.concatenate([prompt, tokens]).astype(np.int32)
    rows = len(prompt) - 1 + np.arange(len(tokens))
    logits = _ref_logits(model, ids, rows)
    return float(np.max(logits.max(-1)
                        - logits[np.arange(len(tokens)), tokens]))


def test_engine_serves_it_with_no_flag_and_agrees_on_logits(model):
    """Bucketed prefill (5, 16 tokens), chunked prefill (30: over the
    largest bucket) and 16-step decode segments through the paged latent
    cache, under ``ServingFrontend``: every served greedy token is the
    reference's best to within ``GAP_TOL``; the page pools have the
    model's shapes, the KV accounting its bytes, and the expert counters
    move."""
    telemetry.reset_telemetry()
    eng = _engine(model)
    cfg = model.config
    assert eng._ks[0].shape == (24 + 2, 8, cfg.kv_lora_rank)
    assert eng._vs[0].shape == (24 + 2, 8, cfg.qk_rope_head_dim)
    assert eng.kv_stats()["bytes_per_token"] == \
        3 * (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * 4
    fe = ServingFrontend(eng, segment=16, max_queue=8)
    prompts = [_ids(n, seed=10 + n) for n in (5, 16, 30)]
    for rid, p in enumerate(prompts):
        fe.submit(p, max_new_tokens=20, rid=rid)
    out = fe.results(wait=True)
    fe.shutdown()
    for rid, p in enumerate(prompts):
        assert out[rid].status == "ok" and len(out[rid].tokens) == 20
        assert _served_gap(model, p, np.asarray(out[rid].tokens)) <= GAP_TOL
    c = telemetry.registry().snapshot()["counters"]
    steps = c["serving.moe_layer_steps_total"]
    assert steps > 0 and steps % 2 == 0              # two sparse layers
    # every live row of a decode step makes top-4 assignments a layer
    assert c["serving.moe_assignments_total"] % 4 == 0
    assert 1 <= c["serving.moe_experts_hit_total"] / steps <= 12
    assert c["serving.moe_load_max_total"] >= steps
    # each consumed segment's counts ride on its fetch's span too
    waits = [e for e in telemetry.tracer().spans()
             if e["name"] == "serving.device_wait"
             and "moe_layer_steps" in e["args"]]
    assert sum(e["args"]["moe_layer_steps"] for e in waits) == steps
    assert sum(e["args"]["moe_experts_hit"] for e in waits) == \
        c["serving.moe_experts_hit_total"]


def test_prefix_hit_with_cow_resume_on_the_latent_layout(model):
    """A second prompt sharing 20 tokens (2.5 pages) with a cached one:
    the shared pages are mapped, the half page copied (CoW over both
    pools), the tail prefilled at a mid-page base through the resume
    program, and the stream still agrees with the full forward."""
    eng = _engine(model)
    eng.start(segment=4)
    pre = _ids(24, seed=40)
    first = eng.submit(np.concatenate([pre, _ids(4, seed=41)]), 6, rid=1)
    while eng.has_work():
        eng.step()
    p2 = np.concatenate([pre[:20], _ids(7, seed=42)])
    second = eng.submit(p2, 8, rid=2)
    while eng.has_work():
        eng.step()
    assert first.status == "ok" and second.status == "ok"
    assert eng.kv_stats()["prefix_tokens_saved"] >= 16
    assert _served_gap(model, p2, np.asarray(second.tokens)) <= GAP_TOL


def test_generate_takes_it_unchanged(model):
    ids = _ids(12, seed=50)[None]
    a = generate(model, paddle.to_tensor(ids), max_new_tokens=5,
                 cache="paged")
    b = generate(model, paddle.to_tensor(ids), max_new_tokens=5,
                 cache="static")
    np.testing.assert_array_equal(np.asarray(a._value), np.asarray(b._value))
    assert _served_gap(model, ids[0], np.asarray(a._value[0, 12:])) <= GAP_TOL


def test_page_export_import_round_trip_on_the_latent_layout(model):
    """``models/transfer.py`` moves pages of both pools: a prefill on one
    engine, its pages exported and imported into another, and the decode
    there continues the colocated stream bit for bit."""
    from paddle_tpu.models.transfer import transfer_pages

    def frontend(role):
        return ServingFrontend(_engine(model, max_slots=2), max_queue=8,
                               segment=4, role=role)

    p = _ids(11, seed=60)
    solo = frontend("both")
    solo.submit(p, max_new_tokens=6, rid=3)
    want = solo.results(wait=True)[3].tokens
    solo.shutdown()
    src, dst = frontend("prefill"), frontend("decode")
    src.submit(p, max_new_tokens=1, rid=3, hold_kv=True)
    assert src.results(wait=True)[3].status == "ok"
    ticket = src.export_pages(3)
    transfer_pages(src, dst, ticket)
    dst.submit(p, max_new_tokens=6, rid=3, token_base=0,
               kv_import=ticket["ticket"])
    got = dst.results(wait=True)[3]
    assert got.status == "ok"
    np.testing.assert_array_equal(got.tokens, want)
    src.shutdown()
    dst.shutdown()


def test_tp_engine_declines_it_by_mechanism(model):
    with pytest.raises(NotImplementedError, match="latent attention"):
        TPShardedEngine(model, max_slots=2, max_len=32)


def test_dense_segment_program_has_no_statistics_output():
    """A dense model's segment program is what it was: seven outputs, no
    statistics operand or result."""
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config

    paddle.seed(1)
    eng = ContinuousBatchingEngine(
        LlamaForCausalLM(llama_tiny_config()), max_slots=2, max_len=32,
        page_size=8, prompt_buckets=(8,))
    eng.warmup(segment=4)
    exe = eng.compiled_programs()[("segment", 4)]
    assert len(jax.tree_util.tree_leaves(exe.out_info)) == \
        5 + 2 * eng._nl
    assert eng._stat_counters == [] and eng._stat_names == ()


# ------------------------------------------------------------- the kernels


@pytest.mark.parametrize("lengths", [[1, 8, 17, 0], [24, 24, 24, 24],
                                     [3, 0, 0, 9]])
def test_paged_mla_attention_matches_its_oracle(lengths):
    from paddle_tpu.ops.pallas.mla_attention import (
        mla_attention_reference, paged_mla_attention)

    k = jax.random.PRNGKey(0)
    b, h, c, r, pages, page = 4, 4, 32, 16, 12, 8
    ql = jax.random.normal(k, (b, h, c))
    qr = jax.random.normal(jax.random.fold_in(k, 1), (b, h, r))
    cp = jax.random.normal(jax.random.fold_in(k, 2), (pages, page, c))
    rp = jax.random.normal(jax.random.fold_in(k, 3), (pages, page, r))
    tables = jnp.asarray(
        np.random.default_rng(1).permutation(12).reshape(4, 3), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_mla_attention(ql, qr, cp, rp, tables, lengths, 0.2)
    want = mla_attention_reference(ql, qr, cp, rp, tables, lengths, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("m,sizes", [
    (64, [0, 3, 0, 5, 1, 0, 0, 9, 2, 0, 0, 0, 7, 0, 4, 6]),
    (64, [0] * 15 + [64]),
    (64, [0] * 16),
    (384, None),
])
def test_moe_gmm_matches_its_oracle(m, sizes):
    from paddle_tpu.ops.pallas.moe_gmm import gmm_reference, moe_gmm

    if sizes is None:        # three row tiles, experts straddling them
        sizes = np.random.default_rng(0).multinomial(300, [1 / 16] * 16)
    sizes = jnp.asarray(sizes, jnp.int32)
    total = int(sizes.sum())
    k = jax.random.PRNGKey(2)
    lhs = jax.random.normal(k, (m, 64))
    rhs = jax.random.normal(jax.random.fold_in(k, 1), (16, 64, 256))
    got = np.asarray(moe_gmm(lhs, rhs, sizes))[:total]
    want = np.asarray(gmm_reference(lhs, rhs, sizes))[:total]
    np.testing.assert_allclose(got, want, atol=1e-4)
