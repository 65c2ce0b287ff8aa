"""Continuous batching over PagedKVCache (VERDICT r4 item 9, stretch).

The engine must be a pure scheduler: greedy outputs are token-identical to
per-request generate(), across mixed prompt lengths, slot retirement and
readmission. Reference kernel-level anchor:
block_multi_head_attention_kernel.cu (the paged cache the slots live in).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate
from paddle_tpu.models.serving import ContinuousBatchingEngine

from _jaxpr import pallas_names, walk


def _model(vocab=211):
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=256, tie_word_embeddings=True)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


def test_continuous_batching_matches_per_request_generate():
    m = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (5, 11, 3, 9, 14, 7)]
    eng = ContinuousBatchingEngine(m, max_slots=3, max_len=128,
                                   page_size=32, prompt_buckets=(16,))
    outs, stats = eng.run(prompts, max_new_tokens=10, segment=4)
    assert stats["useful_tokens"] == 6 * 10
    assert stats["mean_occupancy"] > 0.5
    for i, p in enumerate(prompts):
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=10,
                     cache="paged")._value)[0, p.size:]
        np.testing.assert_array_equal(outs[i], want, err_msg=f"request {i}")


def test_continuous_batching_eos_retires_and_readmits():
    m = _model()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (4, 6, 5, 8)]
    # find a token the model actually emits greedily, use it as eos
    probe = np.asarray(
        generate(m, paddle.to_tensor(prompts[0][None, :]),
                 max_new_tokens=6, cache="paged")._value)[0, 4:]
    eos = int(probe[2])  # stops request 0 after <= 3 tokens
    eng = ContinuousBatchingEngine(m, max_slots=2, max_len=64,
                                   page_size=32, prompt_buckets=(8, 16),
                                   eos_token_id=eos)
    outs, stats = eng.run(prompts, max_new_tokens=12, segment=4)
    assert all(o is not None for o in outs)
    for i, p in enumerate(prompts):
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=12,
                     cache="paged", eos_token_id=eos)._value)[0, p.size:]
        got = outs[i]
        # engine truncates at eos; generate() eos-pads to full width
        np.testing.assert_array_equal(got, want[:len(got)],
                                      err_msg=f"request {i}")
        if eos in want.tolist():
            assert got[-1] == eos


def test_slot_never_advances_past_capacity():
    """A slot at exactly prompt+max_new == max_len must freeze at its
    budget mid-segment (the paged kernel's lengths contract) and still
    emit the full, correct token stream."""
    m = _model()
    p = np.random.RandomState(3).randint(0, 211, (54,)).astype(np.int32)
    eng = ContinuousBatchingEngine(m, max_slots=2, max_len=64,
                                   page_size=32, prompt_buckets=(64,))
    outs, _ = eng.run([p], max_new_tokens=10, segment=4)
    want = np.asarray(
        generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=10,
                 cache="paged")._value)[0, 54:]
    np.testing.assert_array_equal(outs[0], want)


def test_continuous_batching_validates_capacity():
    m = _model()
    eng = ContinuousBatchingEngine(m, max_slots=2, max_len=64,
                                   page_size=32, prompt_buckets=(32,))
    with pytest.raises(ValueError, match="exceeds slot capacity"):
        eng.run([np.arange(60, dtype=np.int32) % 211], max_new_tokens=10)
    # a bucket larger than the slot capacity is refused UP FRONT (prefill
    # writes the whole padded bucket into the slot's pages)
    eng2 = ContinuousBatchingEngine(m, max_slots=2, max_len=32,
                                    page_size=32, prompt_buckets=(64,))
    with pytest.raises(ValueError, match="bucket 64"):
        eng2.run([np.arange(10, dtype=np.int32)], max_new_tokens=4)
    # chunked prefill needs max_len to be a multiple of the chunk width
    eng3 = ContinuousBatchingEngine(m, max_slots=2, max_len=96,
                                    page_size=32, prompt_buckets=(64,))
    with pytest.raises(ValueError, match="multiple of the largest bucket"):
        eng3.run([np.arange(70, dtype=np.int32) % 211], max_new_tokens=4)
    # the bucket helper's own contract (run() pre-validates, so the raise
    # is only reachable through direct use)
    from paddle_tpu.models.serving import _bucket

    with pytest.raises(ValueError, match="exceeds largest bucket"):
        _bucket(100, (32, 64))


def test_chunked_prefill_long_prompts_match_generate():
    """Prompts beyond the largest bucket admit via chunked prefill (full
    chunks at per-slot offsets + padded final chunk) and must emit the
    same greedy tokens as per-request generate() — mixed with short
    requests in the same run."""
    m = _model()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (100, 9, 70, 33, 15)]  # 100/70/33 are chunked
    eng = ContinuousBatchingEngine(m, max_slots=2, max_len=128,
                                   page_size=32, prompt_buckets=(32,))
    outs, stats = eng.run(prompts, max_new_tokens=8, segment=4)
    assert stats["useful_tokens"] == 5 * 8
    for i, p in enumerate(prompts):
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=8,
                     cache="paged")._value)[0, p.size:]
        np.testing.assert_array_equal(outs[i], want, err_msg=f"request {i}")


# ---------------------------------------------- prefill over a cache


def _chunk128_engine(**kw):
    """Shapes the paged flash forward takes (chunk 128, page 128), and no
    two of chunk / max_len / heads / hidden / vocab equal."""
    cfg = LlamaConfig(vocab_size=97, hidden_size=64, intermediate_size=96,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=384,
                      tie_word_embeddings=True)
    paddle.seed(0)
    return ContinuousBatchingEngine(
        LlamaForCausalLM(cfg), max_slots=2, max_len=384, page_size=128,
        prompt_buckets=(128,), seed=3, **kw)


def _prefill_cols():
    from paddle_tpu.core import telemetry

    return (telemetry.counter("serving.prefill_attn_cols_live_total").value(),
            telemetry.counter("serving.prefill_attn_cols_table_total").value())


def test_three_chunk_prompt_and_mid_page_resume_match_generate():
    """A prompt of three chunks (bases 0, 128, 256) and a prompt that
    resumes at a prefix hit's mid-page end (base 170) attend through the
    paged flash forward and emit ``generate()``'s tokens; the two counters
    rise by the bases the host dispatched."""
    eng = _chunk128_engine(prefix_cache=True)
    m = eng.model
    rng = np.random.RandomState(5)
    long_p = rng.randint(0, 97, (300,)).astype(np.int32)
    pre = rng.randint(0, 97, (260,)).astype(np.int32)
    tail_p = np.concatenate(
        [pre[:170], rng.randint(0, 97, (60,)).astype(np.int32)])
    eng.start(segment=4)
    live0, table0 = _prefill_cols()
    reqs = []
    for p in (long_p, pre, tail_p):      # one at a time: pre is cached
        reqs.append(eng.submit(p, 6))    # when tail_p admits
        while eng.has_work():
            eng.step()
    assert eng.kv_stats()["prefix_tokens_saved"] == 170
    for r, p in zip(reqs, (long_p, pre, tail_p)):
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=6,
                     cache="paged")._value)[0, p.size:]
        np.testing.assert_array_equal(np.asarray(r.tokens), want)
    live, table = _prefill_cols()
    # long_p and pre: chunks at 0 and 128, the final one at 256; tail_p:
    # resume at 170 (a page and 42 tokens of pre's second), its 60 tokens
    # in a bucket of 128
    assert live - live0 == 2 * (128 + 256 + 384) + (170 + 128)
    assert table - table0 == 7 * 3 * 128


@pytest.mark.parametrize("g", [1, 2])
def test_prefill_programs_hold_no_chunk_by_max_len_scores(g):
    """The chunk, final and resume programs attend through the kernel: no
    value in them has the chunk width and ``max_len`` beside the head
    axis (the masked composition's ``[g, heads, 128, max_len]`` scores).
    With the kernels off the same search finds that tensor."""
    eng = _chunk128_engine(prefix_cache=True)
    sds = eng._sds
    state = (jax.tree_util.tree_map(sds, eng._param_snapshot()),
             [sds(k) for k in eng._ks], [sds(v) for v in eng._vs])
    i32 = jnp.int32
    chunk = jax.ShapeDtypeStruct((g, 128), i32)
    rows = jax.ShapeDtypeStruct((g, eng._tables_np.shape[1]), i32)
    vec = jax.ShapeDtypeStruct((g,), i32)
    keys = jax.ShapeDtypeStruct((g,) + eng._key_shape, eng._zero_key.dtype)
    programs = {("chunk", g): (eng._chunk_p, (chunk, rows, vec)),
                ("final", g): (eng._final_chunk_p,
                               (chunk, rows, vec, vec, keys)),
                ("resume", 128, g): (eng._resume_p,
                                     (chunk, rows, vec, vec, keys))}

    def score_shaped(jaxpr):
        shapes = [getattr(v.aval, "shape", ()) for eqn, _ in walk(jaxpr)
                  for v in eqn.outvars]
        return [s for s in shapes if 128 in s and 384 in s and 4 in s]

    for key, (jitted, avals) in programs.items():
        jaxpr = jitted.trace(*state, *avals).jaxpr.jaxpr
        assert score_shaped(jaxpr) == [], key
        # one call a layer
        assert pallas_names(jaxpr).count("flash_fwd_paged") == 2, key
    paddle.set_flags({"FLAGS_use_pallas_kernels": False})
    try:
        eng._build_programs()
        jaxpr = eng._chunk_p.trace(*state, chunk, rows, vec).jaxpr.jaxpr
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})
    assert (g, 4, 128, 384) in score_shaped(jaxpr)
