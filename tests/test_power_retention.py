"""Power-retention layers on the serving path: a fixed recurrent state a slot
in the place of pages a token (ISSUE 31).

Everything is float32 on the CPU at a tiny size (hidden 64, 4 query heads
over 2 kv heads of 16, 2 layers) on seeded weights, against the plain
reference's FULL forward (``paddle_tpu/models/reference/
power_retention_plain.py``: the attention form, no cache, no state, no
feature map). Logits are compared, not sampled tokens.

Tolerances. ``LOGIT_TOL`` 2e-5: both sides are float32 (the reference at
``highest``), logits are of order 1, and what separates them is the order of
float32 sums (the state's recurrence against the quadratic form): measured
under 3e-6 here. The same model with its weights rounded to bfloat16 misses
by over 1e-3 (``test_bf16_weights_would_fail_the_tolerance``). ``GAP_TOL`` is
the same bound on the engine's products, where only tokens come out: the
widest gap of a served (greedy) token's reference logit below the
reference's best.
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
    PowerRetentionForCausalLM,
    ServingFrontend,
    TPShardedEngine,
    generate,
    llama_tiny_config,
    power_retention_tiny_config,
)
from paddle_tpu.models.generation import StateCache, sequence_keeps
from paddle_tpu.models.reference import power_retention_plain as ref
from paddle_tpu.ops.pallas import retention as R

LOGIT_TOL = 2e-5
GAP_TOL = 2e-5
VOCAB = 128
KEYS = ("vocab_size hidden_size intermediate_size num_hidden_layers "
        "num_attention_heads num_key_value_heads head_dim rms_norm_eps "
        "rope_theta").split()


@pytest.fixture(scope="module")
def model():
    paddle.seed(31)
    m = PowerRetentionForCausalLM(power_retention_tiny_config())
    m.eval()
    return m


def _m(model):
    return {k: getattr(model.config, k) for k in KEYS}


def _weights(model):
    return {k: p._value for k, p in model.named_parameters()}


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n).astype(np.int32)


def _ref_logits(model, ids, rows=None, weights=None):
    rows = np.arange(len(ids)) if rows is None else rows
    padded = np.zeros(64, np.int32)
    padded[:len(ids)] = ids
    return np.asarray(ref.forward_logits(
        weights or _weights(model), _m(model), padded, rows))


def _served_gap(model, prompt, tokens):
    rows = len(prompt) - 1 + np.arange(len(tokens))
    logits = _ref_logits(model, np.concatenate([prompt, tokens]), rows)
    return float(np.max(logits.max(-1)
                        - logits[np.arange(len(tokens)), tokens]))


def _qkvg(seed, b=3, n=8, kv=2, grp=3, d=16):
    rng = np.random.default_rng(seed)

    def f(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (f(b, n, kv * grp, d), f(b, n, kv, d) / 4, f(b, n, kv, d),
            jax.nn.log_sigmoid(f(b, n, kv) + 3))


# ------------------------------------------------------------ the reference

def test_the_two_copies_of_the_reference_are_one_file():
    import benchmark.reference.power_retention_plain as bench_copy

    with open(ref.__file__) as a, open(bench_copy.__file__) as b:
        assert a.read() == b.read()


@pytest.mark.parametrize("form", ["recurrent", "chunked", "chunked_uneven"])
def test_the_references_three_forms_agree(form):
    q, k, v, logg = (a[0] for a in _qkvg(1, n=12))
    want = ref.retention_attention(q, k, v, logg)
    got = {"recurrent": lambda: ref.retention_recurrent(q, k, v, logg),
           "chunked": lambda: ref.retention_chunked(q, k, v, logg, 4),
           "chunked_uneven": lambda: ref.retention_chunked(q, k, v, logg, 5),
           }[form]()
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n", [1, 7, 12])
def test_the_references_end_state_is_what_its_recurrence_holds(n):
    """``end_state`` (the closed sum) against the recurrence run by hand."""
    _, k, v, logg = (np.asarray(a[0]) for a in _qkvg(3, n=12))
    st = np.zeros((2, 16, 16, 16))
    z = np.zeros((2, 16, 16))
    for t in range(n):
        g = np.exp(logg[t].astype(np.float64))
        kk = k[t][:, :, None] * k[t][:, None, :]
        st = g[:, None, None, None] * st + kk[..., None] * v[t][:, None, None]
        z = g[:, None, None] * z + kk
    s_got, z_got = ref.end_state(k, v, logg, n)
    np.testing.assert_allclose(s_got, st, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(z_got, z, atol=1e-5, rtol=1e-5)
    # the planted fault holds the last token's own chunk alone
    s_win, _ = ref.end_state(k, v, logg, n, window=4)
    lo = (n - 1) // 4 * 4
    if lo:
        assert np.linalg.norm(s_win - st) > 1e-2 * np.linalg.norm(st)
    else:
        np.testing.assert_allclose(s_win, st, atol=1e-5, rtol=1e-5)


def test_a_state_kept_in_bfloat16_reads_as_rounded():
    """The control's form: rounded at every chunk of the prompt and at
    every token after, it misses the float32 sum by bfloat16's step, not
    by float32's."""
    _, k, v, logg = (a[0] for a in _qkvg(4, n=48))
    exact = ref.end_state(k, v, logg, 41)
    low = jax.jit(lambda n, p: ref.end_state(
        k, v, logg, n, p, 8, jnp.bfloat16))(41, 19)
    for got, want in zip(low, exact):
        miss = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert 5e-4 < miss < 3e-2


def test_feature_map_is_the_squared_product_in_d_d_plus_1_over_2_slots():
    """``phi(q) . phi(k) = (q . k)^2``; the layout's R x d slots hold each
    of the d(d+1)/2 monomials once (row d/2 holds its d/2 pairs twice, at
    half the weight)."""
    rng = np.random.default_rng(2)
    for d in (2, 16, 128):
        q, k = (jnp.asarray(rng.standard_normal(d), jnp.float32)
                for _ in range(2))
        pq, pk = R.phi(q), R.phi(k, key_side=True)
        assert pq.shape == (d // 2 + 1, d)
        np.testing.assert_allclose(jnp.sum(pq * pk), jnp.dot(q, k) ** 2,
                                   rtol=1e-4)
        assert pq.size - d // 2 == d * (d + 1) // 2
    (s_shape, _), (z_shape, _) = PowerRetentionForCausalLM(
        power_retention_tiny_config(num_hidden_layers=1)).sequence_state()
    assert s_shape == (2, 9, 16, 16) and z_shape == (2, 9, 16)


# ---------------------------------------------------------------- the model

def test_uncached_forward_matches_the_reference(model):
    ids = _ids(24)
    got = np.asarray(model(paddle.to_tensor(ids[None]))._value[0])
    np.testing.assert_allclose(got, _ref_logits(model, ids), atol=LOGIT_TOL,
                               rtol=0)


def test_bf16_weights_would_fail_the_tolerance(model):
    ids = _ids(24)
    low = {k: v.astype(jnp.bfloat16).astype(v.dtype)
           for k, v in _weights(model).items()}
    got = _ref_logits(model, ids, weights=low)
    assert np.abs(got - _ref_logits(model, ids)).max() > 5 * LOGIT_TOL


def _state_step(model):
    """``step(tokens, ss, zs, rows, length, true_lens, live)`` -> (logits,
    ss, zs), jitted as the engine's programs are."""
    nl = model.config.num_hidden_layers

    def step(tokens, ss, zs, rows, length, true_lens, live):
        length = 0 if length is None else length
        caches = [StateCache(ss[i], zs[i], rows, length, true_lens, live)
                  for i in range(nl)]
        logits, out = model(paddle.to_tensor(tokens), caches=caches)
        return logits._value, [c.s for c in out], [c.z for c in out]

    return jax.jit(step)


def _zero_state(model, slots):
    (s_shape, s_dt), (z_shape, z_dt) = model.sequence_state()
    nl = model.config.num_hidden_layers
    return ([jnp.zeros((slots,) + s_shape, s_dt) for _ in range(nl)],
            [jnp.zeros((slots,) + z_shape, z_dt) for _ in range(nl)])


def test_padded_prefill_chunks_and_decode_through_the_state(model):
    """Two rows in slots 2 and 0: an 11-token prompt in a 16-bucket
    (padding masked out of the state, head at the true last position) and a
    21-token one as a full chunk of 16 then a padded chunk at base 16 (the
    state carried across the boundary); then four decode steps each. Every
    logit row against the reference's full forward."""
    step = _state_step(model)
    a, b = _ids(15, seed=3), _ids(25, seed=4)
    ss, zs = _zero_state(model, 4)
    rows = jnp.asarray([2, 0], jnp.int32)
    first = np.zeros((2, 16), np.int32)
    first[0, :11], first[1] = a[:11], b[:16]
    logits, ss, zs = step(first, ss, zs, rows, None,
                          jnp.asarray([11, 16]), None)
    assert logits.shape == (2, 1, VOCAB)
    np.testing.assert_allclose(logits[0, 0], _ref_logits(model, a, [10])[0],
                               atol=LOGIT_TOL, rtol=0)
    # row 0 rides the scratch slot while row 1 takes its second chunk
    second = np.zeros((2, 16), np.int32)
    second[1, :5] = b[16:21]
    logits, ss, zs = step(second, ss, zs, jnp.asarray([3, 0], jnp.int32),
                          jnp.asarray([0, 16]), jnp.asarray([1, 5]), None)
    np.testing.assert_allclose(logits[1, 0], _ref_logits(model, b, [20])[0],
                               atol=LOGIT_TOL, rtol=0)
    lengths = np.asarray([11, 21])
    for i in range(4):
        tok = np.stack([a[11 + i], b[21 + i]])[:, None]
        logits, ss, zs = step(tok, ss, zs, rows, jnp.asarray(lengths), None,
                              jnp.asarray([True, True]))
        want = np.stack([_ref_logits(model, a, [11 + i])[0],
                         _ref_logits(model, b, [21 + i])[0]])
        np.testing.assert_allclose(logits[:, 0], want, atol=LOGIT_TOL,
                                   rtol=0)
        lengths += 1


@pytest.mark.parametrize("kernels", [True, False], ids=["kernel", "jnp"])
def test_a_row_that_is_not_live_keeps_its_state_bit_for_bit(model, kernels):
    paddle.set_flags({"FLAGS_use_pallas_kernels": kernels})
    try:
        step = _state_step(model)
        ss, zs = _zero_state(model, 3)
        rows = jnp.asarray([0, 1], jnp.int32)
        prompt = np.stack([_ids(8, seed=5), _ids(8, seed=6)])
        _, ss, zs = step(prompt, ss, zs, rows, None, jnp.asarray([8, 8]),
                         None)
        before = [np.asarray(s) for s in ss], [np.asarray(z) for z in zs]
        _, ss, zs = step(np.asarray([[3], [4]], np.int32), ss, zs, rows,
                         jnp.asarray([8, 8]), None,
                         jnp.asarray([True, False]))
        for old, new in zip(before[0] + before[1], ss + zs):
            new = np.asarray(new)
            assert (new[1] == old[1]).all()          # dead row: untouched
            assert not (new[0] == old[0]).all()      # live row: advanced
            assert (new[2] == old[2]).all()
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})


# --------------------------------------------------------------- the engine

def _engine(model, **kw):
    args = dict(max_slots=2, max_len=64, page_size=8, prompt_buckets=(8, 16))
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


def test_the_engine_keeps_a_state_a_slot_and_says_so(model, served):
    eng = served["engine"]
    assert {k[0] for k in sequence_keeps(model)} == {"state"}
    assert eng._ks[0].shape == (2 + 1, 2, 9, 16, 16)
    assert eng._vs[0].shape == (2 + 1, 2, 9, 16)
    assert eng._ks[0].dtype == jnp.float32
    kv = eng.kv_stats()
    per_slot = 2 * (2 * 9 * 16 * 16 + 2 * 9 * 16) * 4
    assert kv["state_bytes_per_slot"] == per_slot
    assert kv["bytes_per_token"] == 0
    assert kv["pages_total"] == kv["pages_free"] == kv["pages_granted"] == 0
    assert kv["slots_live"] == 0 and kv["bytes_in_use"] == 0
    h = served["health"]
    assert h["kv_pages_total"] == 0 and h["kv_pages_free"] == 0
    assert h["kv_slots"] == 2 and 0.0 <= h["kv_occupancy"] <= 1.0


def test_counters_and_spans_of_the_state_path(served):
    c, spans = served["counters"], served["spans"]
    prompt_tokens = sum(PROMPTS.values())
    assert c["serving.state_prefill_tokens_total"] == prompt_tokens
    assert c["serving.state_prefill_padded_total"] > 0
    steps = c["serving.state_layer_steps_total"]
    assert steps > 0 and steps % 2 == 0                  # two layers
    # a live row a layer-step: between one and two rows were decoding
    assert steps <= c["serving.state_rows_live_total"] <= 2 * steps
    waits = [e for e in spans if e["name"] == "serving.device_wait"
             and "state_layer_steps" in e["args"]]
    assert sum(e["args"]["state_layer_steps"] for e in waits) == steps
    resets = [e for e in spans if e["name"] == "serving.state_reset"]
    assert sum(e["args"]["slots"] for e in resets) == len(PROMPTS)
    assert "serving.attn_pages_live_total" not in c


def test_pipeline_on_and_off_are_token_identical(model):
    outs = []
    for pipeline in (True, False):
        eng = _engine(model, pipeline=pipeline)
        out, stats = eng.run([_ids(n, seed=40 + n) for n in (7, 19, 12)],
                             max_new_tokens=10, segment=4)
        assert stats["statuses"] == ["ok"] * 3
        outs.append([o.tolist() for o in out])
    assert outs[0] == outs[1]


def _state_gap(model, eng, req):
    """Worst (layer, kv head) miss of the state ``req`` left in its slot
    against the reference's, over the whole outer product."""
    fed = np.concatenate([req.prompt, req.tokens[:-1]]).astype(np.int32)
    padded = np.zeros(64, np.int32)
    padded[:len(fed)] = fed
    _, want = ref.forward_logits(_weights(model), _m(model), padded, [0],
                                 state_at=len(fed))
    worst = 0.0
    for (s, z), (s_ref, z_ref) in zip(eng.read_state(req.slot), want):
        for got, exp in zip(R.dense_state(s, z), (s_ref, z_ref)):
            exp = np.asarray(exp)
            for c in range(exp.shape[0]):
                worst = max(worst, np.linalg.norm(got[c] - exp[c])
                            / np.linalg.norm(exp[c]))
    return worst


def test_the_state_a_request_leaves_is_the_references(model):
    """Prefill over three chunks and then decode through the state: what
    the slot holds at the end is the reference's sum over the prompt and
    every fed token, to float32."""
    eng = _engine(model).start(segment=4)
    reqs = [eng.submit(_ids(n, seed=60 + n), 11) for n in (37, 6)]
    while eng.has_work():
        eng.step()
    assert [r.status for r in reqs] == ["ok", "ok"]
    assert sorted(r.slot for r in reqs) == [0, 1]
    for r in reqs:
        assert _state_gap(model, eng, r) < 1e-5
    with pytest.raises(ValueError, match="keeps pages"):
        ContinuousBatchingEngine(
            LlamaForCausalLM(llama_tiny_config()), max_slots=2, max_len=32,
            page_size=8, prompt_buckets=(8,)).read_state(0)


@pytest.mark.parametrize("pipeline", [True, False], ids=["pipelined",
                                                         "serial"])
def test_a_failed_fetch_does_not_feed_the_state_twice(model, pipeline):
    """A segment that failed at its fetch may have run: its rows go back
    through prefill (state zeroed, prompt plus tokens), not through a
    replay that would apply the recurrence a second time. The tokens are
    the uninterrupted run's."""
    telemetry.reset_telemetry()
    prompts = [_ids(n, seed=70 + n) for n in (7, 19)]
    want, _ = _engine(model, pipeline=pipeline).run(
        prompts, max_new_tokens=14, segment=4)
    eng = _engine(model, pipeline=pipeline).start(segment=4)
    reqs = [eng.submit(p, 14) for p in prompts]
    consume, calls = eng._consume, []

    def failing(h, finished):
        calls.append(h)
        if len(calls) == 2:
            raise RuntimeError("planted: the fetch failed")
        return consume(h, finished)

    eng._consume = failing
    while eng.has_work():
        eng.step()
    assert [r.status for r in reqs] == ["ok", "ok"]
    assert [r.output().tolist() for r in reqs] == [w.tolist() for w in want]
    for r, p in zip(reqs, prompts):
        assert _served_gap(model, p, r.output()) <= GAP_TOL
    c = telemetry.registry().snapshot()["counters"]
    assert c["serving.state_readmitted"] == 2


def test_a_row_taken_back_twice_retires_as_failed(model):
    eng = _engine(model).start(segment=4)
    req = eng.submit(_ids(9, seed=81), 30)
    consume, calls = eng._consume, []

    def failing(h, finished):
        calls.append(h)
        if len(calls) in (2, 4):
            raise RuntimeError("planted: the fetch failed")
        return consume(h, finished)

    eng._consume = failing
    while eng.has_work():
        eng.step()
    assert req.status == "failed" and "planted" in repr(req.error)


def test_prefix_cache_is_off_whatever_the_argument_says(model, caplog):
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
        eng = _engine(model, prefix_cache=True)
    assert eng._prefix is None
    assert any("prefix cache is off" in r.getMessage()
               for r in caplog.records)
    eng.warmup(segment=4)
    keys = {k[0] for k in eng.compiled_programs()}
    assert keys == {"prefill", "chunk", "final", "segment", "reset"}


@pytest.mark.parametrize("what", ["export_pages", "import_pages",
                                  "hold_kv", "tp_engine", "generate"])
def test_what_is_not_built_refuses_by_name(model, what):
    eng = _engine(model).start(segment=4)
    calls = {
        "export_pages": lambda: eng.export_pages(0),
        "import_pages": lambda: eng.import_kv_chunk({"ticket": "t"}, 0,
                                                    None, None, 0),
        "hold_kv": lambda: eng.submit(_ids(5), 4, hold_kv=True),
        "tp_engine": lambda: TPShardedEngine(model, max_slots=2, max_len=32),
        "generate": lambda: generate(model, paddle.to_tensor(_ids(5)[None]),
                                     max_new_tokens=2),
    }
    with pytest.raises(NotImplementedError,
                       match="PowerRetentionForCausalLM.*ROADMAP M4"):
        calls[what]()


def test_a_page_models_programs_are_what_they_were():
    """A dense model's programs keep their operands and outputs: no state
    row, no true-length operand to the chunk program, no reset program."""
    paddle.seed(1)
    eng = ContinuousBatchingEngine(
        LlamaForCausalLM(llama_tiny_config()), max_slots=2, max_len=32,
        page_size=8, prompt_buckets=(8,))
    assert sequence_keeps(eng.model) == (("pages", (4, 16), (4, 16)),) * 2
    eng.warmup(segment=4)
    progs = eng.compiled_programs()
    assert "reset" not in {k[0] for k in progs}
    seg = progs[("segment", 4)]
    n_params = len(list(eng.model.named_parameters()))
    assert len(jax.tree_util.tree_leaves(seg.in_tree.unflatten(
        [0] * seg.in_tree.num_leaves))) == n_params + 2 * eng._nl + 6
    assert len(jax.tree_util.tree_leaves(seg.out_info)) == 5 + 2 * eng._nl
    chunk = progs[("chunk", 1)]
    assert chunk.in_tree.num_leaves == n_params + 2 * eng._nl + 3
    assert eng._tables_np.shape == (3, 4 + 1)


# -------------------------------------------------------------- the kernels

@pytest.mark.parametrize("true_lens", [None, [8, 5, 1]],
                         ids=["full", "true_lens_inside"])
def test_chunk_kernel_matches_the_jnp_form(true_lens):
    q, k, v, logg = _qkvg(7)
    if true_lens is not None:
        k, logg = R.mask_chunk(k, logg, jnp.asarray(true_lens))
    s_shape, z_shape = R.state_shapes(2, 16)
    rows = jnp.asarray([3, 0, 4], jnp.int32)
    s = jnp.zeros((5,) + s_shape)
    z = jnp.zeros((5,) + z_shape)
    for kern in (False, True):          # a chunk from zero, then one on it
        y0, s0, z0 = R.retention_chunk_reference(q, k, v, logg, s, z, rows)
        y1, s1, z1 = R.power_retention_chunk(q, k, v, logg, s, z, rows)
        # y is a quotient: on random data some denominator (q . k)^2 is
        # all but nought and carries the sums' rounding a few times over
        np.testing.assert_allclose(y1, y0, atol=2e-5, rtol=3e-4)
        np.testing.assert_allclose(s1, s0, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(z1, z0, atol=2e-5, rtol=1e-5)
        s, z = (s1, z1) if kern else (s0, z0)
    if true_lens is not None:
        # the masked tail left no trace: the same state as the short chunk
        _, s2, _ = R.retention_chunk_reference(
            q[1:2, :5], k[1:2, :5], v[1:2, :5], logg[1:2, :5],
            jnp.zeros((5,) + s_shape), jnp.zeros((5,) + z_shape), rows[1:2])
        _, s3, _ = R.retention_chunk_reference(
            q[1:2], k[1:2], v[1:2], logg[1:2], jnp.zeros((5,) + s_shape),
            jnp.zeros((5,) + z_shape), rows[1:2])
        np.testing.assert_allclose(s3[0], s2[0], atol=1e-6, rtol=1e-6)


def test_decode_kernel_matches_the_jnp_form_over_the_live_rows():
    q, k, v, logg = _qkvg(8)
    s_shape, z_shape = R.state_shapes(2, 16)
    rows = jnp.asarray([3, 0, 4], jnp.int32)
    _, s, z = R.retention_chunk_reference(
        q, k, v, logg, jnp.zeros((5,) + s_shape), jnp.zeros((5,) + z_shape),
        rows)
    live = jnp.asarray([True, False, True])
    args = (q[:, 0], k[:, 1], v[:, 2], logg[:, 3], s, z, rows, live)
    y0, s0, z0 = R.retention_decode_reference(*args)
    y1, s1, z1 = R.power_retention_decode(*args)
    np.testing.assert_allclose(np.asarray(y1)[[0, 2]],
                               np.asarray(y0)[[0, 2]], atol=2e-5, rtol=3e-4)
    np.testing.assert_allclose(s1, s0, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(z1, z0, atol=2e-5, rtol=1e-5)
    assert (np.asarray(s1)[0] == np.asarray(s)[0]).all()    # row 1's slot
