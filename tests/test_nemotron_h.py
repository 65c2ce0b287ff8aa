"""The ``nemotron_h`` hybrid on the serving path: Mamba-2 layers (a state a
slot), attention layers (pages a token) and expert layers (nothing) in ONE
model on ONE table (ISSUE 33).

Everything is float32 on the CPU at a tiny size (hidden 64; 4 Mamba-2 heads of
8 in 2 groups, state 16, chunk 16; 4 query heads over 2 kv heads of 16; 8
experts of 32, top 2, shared 48; pattern ``MEM*E``) on seeded weights, against
the plain reference's FULL forward (``paddle_tpu/models/reference/
nemotron_h_plain.py``: the recurrence token by token, plain attention, a loop
over the experts; no cache, no kernel). Logits are compared, not sampled
tokens.

Tolerances. ``LOGIT_TOL`` 2e-5: both sides are float32 (the reference at
``highest``), logits are of order 1, and what separates them is the order of
float32 sums (the chunked form against the recurrence, the sorted grouped
product against the loop over experts): measured under 2e-6 here. The same
model with its weights rounded to bfloat16 misses by over 1e-3
(``test_bf16_weights_would_fail_the_tolerance``). ``GAP_TOL`` is the same
bound on the engine's products, where only tokens come out: the widest gap of
a served (greedy) token's reference logit below the reference's best.
``KERNEL_TOL`` 2e-5 absolute on values of order 1-10 with 1e-5 relative: a
kernel and its jnp form sum the same float32 products in another order.
"""
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import telemetry
from paddle_tpu.models import (
    ContinuousBatchingEngine,
    LlamaForCausalLM,
    MoEMLAForCausalLM,
    NemotronHForCausalLM,
    PowerRetentionForCausalLM,
    ServingFrontend,
    TPShardedEngine,
    generate,
    llama_tiny_config,
    moe_mla_tiny_config,
    nemotron_h_tiny_config,
    power_retention_tiny_config,
)
from paddle_tpu.models.generation import (LayerPass, SequenceStore,
                                          sequence_keeps)
from paddle_tpu.models.nemotron_h import STEP_STAT_NAMES
from paddle_tpu.models.reference import nemotron_h_plain as ref
from paddle_tpu.ops.pallas import ssd as S

LOGIT_TOL = 2e-5
GAP_TOL = 2e-5
KERNEL_TOL = dict(atol=2e-5, rtol=1e-5)
VOCAB = 128
KEYS = ("vocab_size hidden_size num_hidden_layers hybrid_override_pattern "
        "mamba_num_heads mamba_head_dim n_groups ssm_state_size conv_kernel "
        "chunk_size num_attention_heads num_key_value_heads head_dim "
        "n_routed_experts num_experts_per_tok moe_intermediate_size "
        "moe_shared_expert_intermediate_size routed_scaling_factor "
        "norm_topk_prob layer_norm_epsilon").split()


def _build(seed=33, **cfg):
    paddle.seed(seed)
    m = NemotronHForCausalLM(nemotron_h_tiny_config(**cfg))
    m.eval()
    # a correction bias that changes choices, as the benchmark's
    for name, p in m.named_parameters():
        if name.endswith("e_score_correction_bias"):
            p._value = 0.02 * jax.random.normal(
                jax.random.PRNGKey(len(name)), p.shape, jnp.float32)
    return m


@pytest.fixture(scope="module")
def model():
    return _build()


def _m(model):
    return {k: getattr(model.config, k) for k in KEYS}


def _weights(model):
    return {k: p._value for k, p in model.named_parameters()}


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _ref_logits(model, ids, rows=None, weights=None, **kw):
    rows = np.arange(len(ids)) if rows is None else rows
    padded = np.zeros(64, np.int32)
    padded[:len(ids)] = ids
    kw.setdefault("experts_held", model.config.experts_held)
    return ref.forward_logits(weights or _weights(model), _m(model), padded,
                              rows, **kw)


def _served_gap(model, prompt, tokens):
    rows = len(prompt) - 1 + np.arange(len(tokens))
    logits = np.asarray(_ref_logits(model, np.concatenate([prompt, tokens]),
                                    rows))
    return float(np.max(logits.max(-1)
                        - logits[np.arange(len(tokens)), tokens]))


def _ssd_operands(seed, b=3, n=16, h=4, p=8, g=2, ns=16):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    dt = jax.nn.softplus(f(b, n, h) - 2)
    return f(b, n, h, p), dt, -dt * jnp.exp(f(h)), f(b, n, g, ns), \
        f(b, n, g, ns)


# ------------------------------------------------------------ the reference

def test_the_two_copies_of_the_reference_are_one_file():
    import benchmark.reference.nemotron_h_plain as bench_copy

    with open(ref.__file__) as a, open(bench_copy.__file__) as b:
        assert a.read() == b.read()


@pytest.mark.parametrize("chunk", [16, 5, 64], ids=["chunk16", "ragged",
                                                    "one_chunk"])
def test_the_references_recurrent_and_chunked_forms_agree(chunk):
    x, dt, da, bm, cm = (a[0] for a in _ssd_operands(1, n=40))
    want, _ = ref.ssd_recurrent(x, dt, da, bm, cm)
    got = ref.ssd_chunked(x, dt, da, bm, cm, chunk)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("n", [1, 17, 40])
def test_the_references_end_state_is_what_its_recurrence_holds(n):
    x, dt, da, bm, cm = (a[0] for a in _ssd_operands(2, n=40))
    _, at_n = ref.ssd_recurrent(x, dt, da, bm, cm, state_at=jnp.int32(n))
    _, whole = ref.ssd_recurrent(x[:n], dt[:n], da[:n], bm[:n], cm[:n],
                                 state_at=jnp.int32(n))
    np.testing.assert_allclose(at_n, whole, atol=1e-6, rtol=1e-6)
    assert float(jnp.abs(at_n).max()) > 0


def test_a_state_kept_in_bfloat16_reads_as_rounded():
    x, dt, da, bm, cm = (a[0] for a in _ssd_operands(3, n=40))
    _, exact = ref.ssd_recurrent(x, dt, da, bm, cm, state_at=jnp.int32(40))
    _, low = ref.ssd_recurrent(x, dt, da, bm, cm, chunk=16,
                               state_dtype=jnp.bfloat16,
                               state_at=jnp.int32(40), prefill=jnp.int32(24))
    miss = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-4 < miss < 2e-2         # bfloat16 keeps 8 bits
    assert (np.asarray(low) == np.asarray(
        low.astype(jnp.bfloat16).astype(jnp.float32))).all()


def test_the_planted_fault_drops_what_a_chunk_carries(model):
    ids = _ids(40)
    whole = np.asarray(_ref_logits(model, ids))
    cut = np.asarray(_ref_logits(model, ids, window=16))
    np.testing.assert_allclose(cut[:16], whole[:16], atol=LOGIT_TOL, rtol=0)
    assert np.abs(cut[16:] - whole[16:]).max() > 100 * LOGIT_TOL


# ---------------------------------------------------------------- the model

def test_uncached_forward_matches_the_reference(model):
    ids = _ids(40)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, np.asarray(_ref_logits(model, ids)),
                               atol=LOGIT_TOL, rtol=0)


def test_bf16_weights_would_fail_the_tolerance(model):
    ids = _ids(40)
    low = {k: v.astype(jnp.bfloat16).astype(v.dtype)
           for k, v in _weights(model).items()}
    got = np.asarray(_ref_logits(model, ids, weights=low))
    assert np.abs(got - np.asarray(_ref_logits(model, ids))).max() \
        > 5 * LOGIT_TOL


def test_the_attention_takes_its_head_size_from_the_config():
    """hidden 64 over 4 heads would be 16; the config says 32, and q is
    128 wide."""
    m = _build(head_dim=32, num_hidden_layers=2,
               hybrid_override_pattern="M*")
    attn = m.model.layers[1].mixer
    assert attn.q_proj.weight.shape == [64, 4 * 32]
    assert attn.k_proj.weight.shape == [64, 2 * 32]
    assert not any("rope" in n for n, _ in m.named_buffers())
    assert sequence_keeps(m)[1] == ("pages", (2, 32), (2, 32))
    ids = _ids(24)
    got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, np.asarray(_ref_logits(m, ids)),
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("held", [(0, 8), (0, 4), (4, 4)],
                         ids=["all", "first_half", "second_half"])
def test_a_share_of_the_experts_matches_the_references_share(held):
    m = _build(experts_held=held, num_hidden_layers=2,
               hybrid_override_pattern="ME")
    ids = _ids(24)
    got = np.asarray(m(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, np.asarray(_ref_logits(m, ids)),
                               atol=LOGIT_TOL, rtol=0)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Experts [0, 4) on one chip and [4, 8) on the other, the shared expert
    counted once: the two routed parts and the shared part add up to the
    reference's whole layer."""
    whole = _build(num_hidden_layers=1, hybrid_override_pattern="E")
    w = _weights(whole)
    u = jnp.asarray(np.random.default_rng(4).standard_normal((24, 64)),
                    jnp.float32)
    lw = {k[len("model.layers.0."):]: v for k, v in w.items()
          if k.startswith("model.layers.0.")}
    want = ref.expert_mixer(u, lw, _m(whole), ref.mm_f32)
    parts = []
    for first in (0, 4):
        half = _build(num_hidden_layers=1, hybrid_override_pattern="E",
                      experts_held=(first, 4))
        mixer = half.model.layers[0].mixer
        mixer.experts_up._value = lw["mixer.experts_up"][first:first + 4]
        mixer.experts_down._value = lw["mixer.experts_down"][first:first + 4]
        for name in ("gate.weight", "e_score_correction_bias"):
            obj = mixer
            for part in name.split(".")[:-1]:
                obj = getattr(obj, part)
            getattr(obj, name.split(".")[-1])._value = lw["mixer." + name]
        routed, _ = mixer._routed(u, *_route(mixer, u), None)
        parts.append(routed)
    shared = ref.relu2_mlp(u, lw["mixer.shared_experts.up_proj.weight"],
                           lw["mixer.shared_experts.down_proj.weight"],
                           ref.mm_f32)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               atol=LOGIT_TOL, rtol=0)
    only_first = ref.expert_mixer(u, lw, _m(whole), ref.mm_f32, (0, 4),
                                  shared=False)
    np.testing.assert_allclose(parts[0], only_first, atol=LOGIT_TOL, rtol=0)


def _route(mixer, u):
    from paddle_tpu.models.moe_mla import route

    c = mixer.config
    return route(u, mixer.gate.weight._value,
                 mixer.e_score_correction_bias._value, c.num_experts_per_tok,
                 c.routed_scaling_factor, c.norm_topk_prob)


def _step(model, page=8):
    """``step(tokens, ks, vs, tables, length, true_lens, live)`` -> (logits,
    ks, vs, stats), jitted as the engine's programs are, over the engine's
    own store."""
    store = SequenceStore(sequence_keeps(model), jnp.float32, page, 8, True)

    def step(tokens, ks, vs, tables, length, true_lens, live):
        length = 0 if length is None else length
        caches = store.caches(ks, vs, tables, length, live=live,
                              true_lens=true_lens)
        logits, out = model(paddle.to_tensor(tokens), caches=caches)
        counted = [c.stats for c in out if c.stats is not None]
        return (logits._value, *store.pools(out),
                sum(counted) if counted else None)

    return store, jax.jit(step)


def _tables(store, rows_pages):
    """A table row a sequence: its pages, then its slot."""
    return jnp.asarray([pages + [slot] for slot, pages in rows_pages],
                       jnp.int32)


def test_padded_prefill_chunks_and_decode_through_state_and_pages(model):
    """Two rows in slots 2 and 0: an 11-token prompt in a 16-bucket (padding
    masked out of the state and of the carried inputs, pages written, head
    at the true last position) and a 21-token one as a full chunk of 16
    then a padded chunk at base 16 (state and carried inputs carried across
    the boundary, the chunk attends to the pages the first wrote); then four
    decode steps each. Every logit row against the reference's full
    forward."""
    store, step = _step(model)
    a, b = _ids(15, seed=3), _ids(25, seed=4)
    ks, vs = store.allocate(9, 3)            # 8 pages + a scratch page
    tab = _tables(store, [(2, [0, 1, 2, 3]), (0, [4, 5, 6, 7])])
    first = np.zeros((2, 16), np.int32)
    first[0, :11], first[1] = a[:11], b[:16]
    logits, ks, vs, _ = step(first, ks, vs, tab, None,
                             jnp.asarray([11, 16]), None)
    assert logits.shape == (2, 1, VOCAB)
    np.testing.assert_allclose(logits[0, 0], _ref_logits(model, a, [10])[0],
                               atol=LOGIT_TOL, rtol=0)
    # row 0 rides the scratch slot and the scratch page while row 1 takes
    # its second chunk
    second = np.zeros((2, 16), np.int32)
    second[1, :5] = b[16:21]
    tab2 = _tables(store, [(3, [8, 8, 8, 8]), (0, [4, 5, 6, 7])])
    logits, ks, vs, _ = step(second, ks, vs, tab2, jnp.asarray([0, 16]),
                             jnp.asarray([1, 5]), None)
    np.testing.assert_allclose(logits[1, 0], _ref_logits(model, b, [20])[0],
                               atol=LOGIT_TOL, rtol=0)
    lengths = np.asarray([11, 21])
    for i in range(4):
        tok = np.stack([a[11 + i], b[21 + i]])[:, None]
        logits, ks, vs, stats = step(tok, ks, vs, tab, jnp.asarray(lengths),
                                     None, jnp.asarray([True, True]))
        want = np.stack([_ref_logits(model, a, [11 + i])[0],
                         _ref_logits(model, b, [21 + i])[0]])
        np.testing.assert_allclose(logits[:, 0], want, atol=LOGIT_TOL,
                                   rtol=0)
        lengths += 1
    counted = dict(zip(STEP_STAT_NAMES, np.asarray(stats)))
    assert counted["state_layer_steps"] == 2 and \
        counted["state_rows_live"] == 4
    assert counted["moe_layer_steps"] == 2 and \
        counted["moe_assignments"] == 2 * 2 * 2


def test_padding_leaves_state_and_carried_inputs_as_the_unpadded_run(model):
    """An 11-token prompt in a 16-bucket against the same prompt as a chunk
    of exactly 11: state and carried inputs equal, to float32 sums."""
    store, step = _step(model)
    a = _ids(11, seed=7)
    tab = _tables(store, [(1, [0, 1, 2, 3])])
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = a
    _, ks, vs, _ = step(padded, *store.allocate(5, 2), tab, None,
                        jnp.asarray([11]), None)
    _, ks2, vs2, _ = step(a[None], *store.allocate(5, 2), tab, None,
                          jnp.asarray([11]), None)
    for (s, z), (s2, z2) in zip(store.states(ks, vs),
                                store.states(ks2, vs2)):
        np.testing.assert_allclose(s[1], s2[1], atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(z[1], z2[1])
        assert float(jnp.abs(z[1]).max()) > 0
    want = _ref_logits(model, a, [10], state_at=11)[1]
    for (s, z), (ws, wz) in zip(store.states(ks, vs), want):
        np.testing.assert_allclose(s[1], ws, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(z[1], wz, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("kernels", [True, False], ids=["kernel", "jnp"])
def test_a_row_that_is_not_live_keeps_state_and_pages_bit_for_bit(model,
                                                                  kernels):
    paddle.set_flags({"FLAGS_use_pallas_kernels": kernels})
    try:
        store, step = _step(model)
        ks, vs = store.allocate(9, 2)
        tab = _tables(store, [(0, [0, 1, 2, 3]), (1, [4, 5, 6, 7])])
        prompt = np.stack([_ids(8, seed=5), _ids(8, seed=6)])
        _, ks, vs, _ = step(prompt, ks, vs, tab, None, jnp.asarray([8, 8]),
                            None)
        before = [np.asarray(a) for a in ks + vs]
        _, ks, vs, _ = step(np.asarray([[3], [4]], np.int32), ks, vs, tab,
                            jnp.asarray([8, 8]), None,
                            jnp.asarray([True, False]))
        is_state = [k is not None and k[0] == "state"
                    for k in sequence_keeps(model) if k is not None] * 2
        for old, new, state in zip(before, ks + vs, is_state):
            new = np.asarray(new)
            if state:
                assert (new[1] == old[1]).all()      # dead row: untouched
                assert not (new[0] == old[0]).all()  # live row: advanced
                assert (new[2] == old[2]).all()      # the scratch slot
            else:
                # the dead row's first page (4) holds its 8 tokens and stays
                # as it was; a frozen row re-writes only its own next cell
                # (page 5, offset 0), which nothing reads
                assert (new[4] == old[4]).all()
                assert (new[5][1:] == old[5][1:]).all()
                assert (new[6:8] == old[6:8]).all()
                assert (new[0] == old[0]).all()
                assert not (new[1][0] == old[1][0]).all()  # row 0's ninth
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})


# --------------------------------------------------------------- the engine

def _engine(model, **kw):
    args = dict(max_slots=2, max_len=64, page_size=8, prompt_buckets=(16,))
    args.update(kw)
    return ContinuousBatchingEngine(model, **args)


PROMPTS = {"padding_masked": 5, "state_carried_over_chunks": 37,
           "bucket_exact": 16, "admitted_in_a_later_turn": 30,
           "slot_reused_after_a_retire": 11}


@pytest.fixture(scope="module")
def served(model):
    """Five requests over two slots under ``ServingFrontend``: the later
    ones are admitted in later turns, into slots others retired from."""
    telemetry.reset_telemetry()
    eng = _engine(model)
    fe = ServingFrontend(eng, segment=4, max_queue=8)
    prompts = {name: _ids(n, seed=20 + n) for name, n in PROMPTS.items()}
    for rid, p in enumerate(prompts.values()):
        fe.submit(p, max_new_tokens=9 + rid, rid=rid)
    health = fe.health()
    out = fe.results(wait=True)
    fe.shutdown()
    snap = telemetry.registry().snapshot()["counters"]
    spans = list(telemetry.tracer().spans())
    return {"out": out, "prompts": prompts, "counters": snap, "spans": spans,
            "health": health, "engine": eng}


@pytest.mark.parametrize("name", list(PROMPTS))
def test_engine_serves_it_and_agrees_on_logits(model, served, name):
    rid = list(PROMPTS).index(name)
    res = served["out"][rid]
    assert res.status == "ok" and len(res.tokens) == 9 + rid
    assert _served_gap(model, served["prompts"][name],
                       np.asarray(res.tokens)) <= GAP_TOL


def test_the_engine_says_what_each_layer_keeps(model, served):
    eng = served["engine"]
    keeps = sequence_keeps(model)
    assert [k and k[0] for k in keeps] == ["state", None, "state", "pages",
                                           None]
    assert keeps[0] == ("state", ((4, 8, 16), jnp.float32),
                        ((3, 4 * 8 + 2 * 2 * 16), jnp.float32))
    assert keeps[3] == ("pages", (2, 16), (2, 16))
    assert eng._state and eng._paged
    # three layers hold arrays: two states (a row a slot + the scratch
    # slot) and one pool (one full-length sequence a slot + scratch pages)
    assert [a.shape for a in eng._ks] == [(3, 4, 8, 16), (3, 4, 8, 16),
                                          (2 * 8 + 2, 8, 2, 16)]
    assert [a.shape for a in eng._vs] == [(3, 3, 96), (3, 3, 96),
                                          (2 * 8 + 2, 8, 2, 16)]
    assert eng._ks[0].dtype == jnp.float32
    # ONE table: a slot's pages, the write-scratch columns, its state row
    assert eng._tables_np.shape == (3, 8 + 2 + 1)
    assert list(eng._tables_np[:, -1]) == [0, 1, 2]
    kv = eng.kv_stats()
    assert kv["state_bytes_per_slot"] == 2 * (4 * 8 * 16 + 3 * 96) * 4
    assert kv["bytes_per_token"] == 2 * 2 * 16 * 4
    assert kv["pages_total"] == 16 and kv["pages_granted"] == 0
    assert kv["slots_live"] == 0 and kv["bytes_in_use"] == 0
    h = served["health"]
    assert h["kv_pages_total"] == 16 and h["kv_slots"] == 2


def test_counters_and_spans_of_both_paths(served):
    c, spans = served["counters"], served["spans"]
    prompt_tokens = sum(PROMPTS.values())
    assert c["serving.state_prefill_tokens_total"] == prompt_tokens
    assert c["serving.state_prefill_padded_total"] > 0
    steps = c["serving.state_layer_steps_total"]
    assert steps > 0 and steps % 2 == 0          # two Mamba-2 layers
    assert steps <= c["serving.state_rows_live_total"] <= 2 * steps
    # two expert layers beside two Mamba-2 layers; an expert layer counts
    # only the steps in which some row was live
    assert 0 < c["serving.moe_layer_steps_total"] <= steps
    assert c["serving.moe_assignments_total"] == \
        2 * c["serving.state_rows_live_total"]    # top 2, every expert held
    assert 0 < c["serving.moe_experts_hit_total"] <= \
        c["serving.moe_assignments_total"]
    waits = [e for e in spans if e["name"] == "serving.device_wait"
             and "state_layer_steps" in e["args"]]
    assert sum(e["args"]["state_layer_steps"] for e in waits) == steps
    assert sum(e["args"]["moe_layer_steps"] for e in waits) == \
        c["serving.moe_layer_steps_total"]
    resets = [e for e in spans if e["name"] == "serving.state_reset"]
    assert sum(e["args"]["slots"] for e in resets) == len(PROMPTS)
    # the page side keeps its counters: a hybrid attends to columns
    assert c["serving.attn_pages_live_total"] > 0
    assert c["serving.prefill_attn_cols_live_total"] > 0


def test_pipeline_on_and_off_are_token_identical(model):
    outs = []
    for pipeline in (True, False):
        eng = _engine(model, pipeline=pipeline)
        prompts = [_ids(n, seed=40 + n) for n in (7, 33, 16)]
        out, _ = eng.run(prompts, max_new_tokens=10, segment=4)
        outs.append([list(o) for o in out])
    assert outs[0] == outs[1]


def test_the_state_a_request_leaves_is_the_references(model):
    eng = _engine(model).start(segment=4)
    prompt = _ids(21, seed=9)
    req = eng.submit(prompt, 6)
    while eng.has_work():
        eng.step()
    assert req.status == "ok"
    toks = np.asarray(req.output())
    got = eng.read_state(req.slot)
    assert len(got) == 2                          # the Mamba-2 layers
    ids = np.concatenate([prompt, toks])
    _, want = _ref_logits(model, ids, [0], state_at=len(ids) - 1)
    for (s, z), (ws, wz) in zip(got, want):
        np.testing.assert_allclose(s, ws, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(z, wz, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "serial"])
def test_a_failed_fetch_does_not_feed_the_state_twice(model, pipeline):
    """A segment whose fetch fails has already advanced the state in place:
    its rows go back through prefill over a reset slot, and the tokens are
    those of the undisturbed run."""
    prompts = [_ids(n, seed=60 + n) for n in (9, 20)]
    clean, _ = _engine(model, pipeline=pipeline).run(
        prompts, max_new_tokens=8, segment=4)
    eng = _engine(model, pipeline=pipeline)
    real, failed = eng._consume, []

    def consume(h, finished):
        if not failed and eng._seg_runs >= 1:
            failed.append(True)
            raise RuntimeError("planted fetch failure")
        return real(h, finished)

    eng._consume = consume
    out, _ = eng.run(prompts, max_new_tokens=8, segment=4)
    assert failed and [list(o) for o in out] == [list(o) for o in clean]


def test_prefix_cache_is_off_whatever_the_argument_says(model, caplog):
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
        eng = _engine(model, prefix_cache=True)
    assert eng._prefix is None
    assert any("prefix cache is off" in r.getMessage()
               for r in caplog.records)
    eng.warmup(segment=4)
    keys = {k[0] for k in eng.compiled_programs()}
    assert keys == {"prefill", "chunk", "final", "segment", "reset"}


@pytest.mark.parametrize("what", ["export_pages", "import_pages", "hold_kv",
                                  "tp_engine", "generate", "small_pool",
                                  "labels", "attn_mask"])
def test_what_is_not_built_refuses_by_name(model, what):
    eng = _engine(model).start(segment=4)
    ids = paddle.to_tensor(_ids(5)[None])
    calls = {
        "export_pages": lambda: eng.export_pages(0),
        "import_pages": lambda: eng.import_kv_chunk({"ticket": "t"}, 0,
                                                    None, None, 0),
        "hold_kv": lambda: eng.submit(_ids(5), 4, hold_kv=True),
        "tp_engine": lambda: TPShardedEngine(model, max_slots=2, max_len=32),
        "generate": lambda: generate(model, ids, max_new_tokens=2),
        "small_pool": lambda: _engine(model, pool_pages=12),
        "labels": lambda: model(ids, labels=ids),
        "attn_mask": lambda: model(ids, attn_mask=ids),
    }
    match = ("nemotron_h" if what in ("labels", "attn_mask")
             else "NemotronHForCausalLM.*ROADMAP M4")
    with pytest.raises(NotImplementedError, match=match):
        calls[what]()


def _program_shape(eng, segment=4):
    eng.warmup(segment=segment)
    progs = eng.compiled_programs()
    seg = progs[("segment", segment)]
    return ({k[0] for k in progs},
            len(jax.tree_util.tree_leaves(seg.in_tree.unflatten(
                [0] * seg.in_tree.num_leaves))),
            len(jax.tree_util.tree_leaves(seg.out_info)),
            progs[("chunk", 1)].in_tree.num_leaves)


@pytest.mark.parametrize("family", ["dense", "latent", "state"])
def test_the_uniform_models_programs_are_what_they_were(family):
    """Every layer answers alike: the dense, latent and state models'
    programs keep their operands and outputs (two arrays a layer, the table
    of before, the statistics only where the model counts), with no state
    row beside pages and no reset where there is no state."""
    paddle.seed(1)
    make, cfg, stats, state = {
        "dense": (LlamaForCausalLM, llama_tiny_config(), 0, False),
        "latent": (MoEMLAForCausalLM, moe_mla_tiny_config(), 1, False),
        "state": (PowerRetentionForCausalLM, power_retention_tiny_config(),
                  1, True)}[family]
    eng = ContinuousBatchingEngine(make(cfg), max_slots=2, max_len=32,
                                   page_size=8, prompt_buckets=(8,))
    nl = cfg.num_hidden_layers
    assert len(set(sequence_keeps(eng.model))) == 1
    assert len(eng._ks) == len(eng._vs) == nl
    keys, n_in, n_out, chunk_in = _program_shape(eng)
    n_params = len(list(eng.model.named_parameters()))
    assert ("reset" in keys) == state
    assert ("export" in keys) == (not state)
    assert n_in == n_params + 2 * nl + 6
    assert n_out == 5 + 2 * nl + stats
    assert chunk_in == n_params + 2 * nl + 3
    assert eng._tables_np.shape == ((3, 1) if state else (3, 4 + 1))
    assert eng._paged != state and eng._state == state


def test_the_hybrids_programs_hold_an_array_pair_a_keeping_layer(model):
    eng = _engine(model)
    keys, n_in, n_out, chunk_in = _program_shape(eng)
    n_params = len(list(model.named_parameters()))
    held = sum(k is not None for k in sequence_keeps(model))
    assert held == 3 and keys == {"prefill", "chunk", "final", "segment",
                                  "reset"}
    assert n_in == n_params + 2 * held + 6
    assert n_out == 5 + 2 * held + 1         # ONE statistics vector
    assert chunk_in == n_params + 2 * held + 3


def test_a_layer_that_keeps_nothing_gets_live_in_and_hands_stats_out(model):
    store, _ = _step(model)
    live = jnp.asarray([True, False])
    caches = store.caches(*store.allocate(9, 2),
                          _tables(store, [(0, [0] * 4), (1, [1] * 4)]),
                          jnp.asarray([1, 1]), live=live)
    passes = [c for c in caches if isinstance(c, LayerPass)]
    assert len(passes) == 2 and all(c.live is live for c in passes)
    _, out = model(paddle.to_tensor(np.zeros((2, 1), np.int32)),
                   caches=caches)
    for c, keep in zip(out, sequence_keeps(model)):
        if keep is None:                     # an expert layer: one live row
            got = dict(zip(STEP_STAT_NAMES, np.asarray(c.stats)))
            assert got["moe_assignments"] == 2 and got["moe_layer_steps"] == 1
            assert got["state_layer_steps"] == 0


# -------------------------------------------------------------- the kernels

@pytest.mark.parametrize("true_lens", [None, [16, 5, 1]],
                         ids=["full", "true_lens_inside"])
def test_chunk_kernel_matches_the_jnp_form(true_lens):
    x, dt, da, bm, cm = _ssd_operands(7)
    if true_lens is not None:
        dt = S.mask_steps(dt, jnp.asarray(true_lens))
        da = jnp.where(dt > 0, da, 0.0)
    rows = jnp.asarray([3, 0, 4], jnp.int32)
    state = jnp.zeros((5, 4, 8, 16))
    for kern in (False, True):          # a chunk from zero, then one on it
        y0, s0 = S.ssd_chunk_reference(x, dt, da, bm, cm, state, rows)
        y1, s1 = S.ssd_chunk(x, dt, da, bm, cm, state, rows)
        np.testing.assert_allclose(y1, y0, **KERNEL_TOL)
        np.testing.assert_allclose(s1, s0, **KERNEL_TOL)
        state = s1 if kern else s0
    if true_lens is not None:
        # the masked tail left no trace: the same state as the short chunk
        _, s2 = S.ssd_chunk_reference(
            x[1:2, :5], dt[1:2, :5], da[1:2, :5], bm[1:2, :5], cm[1:2, :5],
            jnp.zeros((1, 4, 8, 16)), jnp.asarray([0], jnp.int32))
        _, s3 = S.ssd_chunk_reference(x, dt, da, bm, cm,
                                      jnp.zeros((5, 4, 8, 16)), rows)
        np.testing.assert_allclose(s3[0], s2[0], **KERNEL_TOL)


@pytest.mark.parametrize("heads_block", [2, 4])
def test_decode_kernel_matches_the_jnp_form_over_the_live_rows(heads_block):
    x, dt, da, bm, cm = (a[:, 0] for a in _ssd_operands(8, b=4))
    rng = np.random.default_rng(9)
    state = jnp.asarray(rng.standard_normal((6, 4, 8, 16)), jnp.float32)
    rows = jnp.asarray([4, 1, 0, 3], jnp.int32)
    live = jnp.asarray([True, False, True, False])
    y0, s0 = S.ssd_decode_reference(x, dt, jnp.exp(da), bm, cm, state, rows,
                                    live)
    y1, s1 = S.ssd_decode(x, dt, jnp.exp(da), bm, cm, state, rows, live,
                          heads_block=heads_block)
    np.testing.assert_allclose(np.asarray(y1)[[0, 2]],
                               np.asarray(y0)[[0, 2]], **KERNEL_TOL)
    np.testing.assert_allclose(s1, s0, **KERNEL_TOL)
    for dead in (1, 3, 2, 5):           # dead rows' slots and unused slots
        assert (np.asarray(s1)[dead] == np.asarray(state)[dead]).all()


def test_the_convolution_carries_the_last_real_inputs():
    rng = np.random.default_rng(10)
    xbc = jnp.asarray(rng.standard_normal((2, 8, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((6,)), jnp.float32)
    carried = jnp.asarray(rng.standard_normal((2, 3, 6)), jnp.float32)
    out, keep = S.causal_conv(xbc, w, b, carried, jnp.asarray([8, 2]))
    window = np.concatenate([carried, xbc], 1)
    want = b + sum(w[i] * window[:, i:i + 8] for i in range(4))
    np.testing.assert_allclose(out, want, atol=1e-6)
    np.testing.assert_array_equal(keep[0], xbc[0, 5:])
    # two real tokens: the last carried input, then the two
    np.testing.assert_array_equal(keep[1], window[1, 2:5])
