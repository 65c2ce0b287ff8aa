"""Pallas decode-serving kernels: paged / masked decode attention
(analogs of block_multi_head_attention_kernel.cu and
masked_multihead_attention_kernel.cu) — numerics vs the jnp composition,
plus end-to-end generation equivalence across cache types.
"""
import math

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.decode_attention import (
    _work_list,
    masked_decode_attention,
    paged_attention,
)


def _ref_decode(q, k, v, lens):
    b_, h_, d_ = q.shape
    g = h_ // k.shape[2]
    o = np.zeros((b_, h_, d_), np.float32)
    for b in range(b_):
        if not int(lens[b]):
            continue  # nothing to attend to: zeros
        kk = np.asarray(k)[b, :int(lens[b])]
        vv = np.asarray(v)[b, :int(lens[b])]
        for h in range(h_):
            s = kk[:, h // g] @ np.asarray(q)[b, h] / math.sqrt(d_)
            p = np.exp(s - s.max())
            p /= p.sum()
            o[b, h] = p @ vv[:, h // g]
    return o


@pytest.mark.parametrize("kvh", [4, 2], ids=["mha", "gqa"])
def test_masked_decode_attention_matches_reference(kvh):
    rng = np.random.RandomState(0)
    B, H, D, L = 2, 4, 64, 256
    q = jnp.asarray(rng.rand(B, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, L, kvh, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, L, kvh, D).astype(np.float32))
    lens = jnp.asarray([100, 256], jnp.int32)
    out = masked_decode_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), _ref_decode(q, k, v, lens),
                               rtol=2e-5, atol=2e-6)


def test_paged_attention_scattered_tables():
    rng = np.random.RandomState(1)
    B, H, KVH, D = 2, 4, 4, 64
    PAGE, NPAGES = 32, 16
    q = jnp.asarray(rng.rand(B, H, D).astype(np.float32))
    k_pages = jnp.asarray(rng.rand(NPAGES, PAGE, KVH, D).astype(np.float32))
    v_pages = jnp.asarray(rng.rand(NPAGES, PAGE, KVH, D).astype(np.float32))
    tables = jnp.asarray([[3, 7, 1, 0], [9, 2, 15, 4]], jnp.int32)
    lens = jnp.asarray([100, 70], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, tables, lens)

    o = np.zeros((B, H, D), np.float32)
    for b in range(B):
        kk = np.concatenate(
            [np.asarray(k_pages)[p] for p in np.asarray(tables)[b]],
            0)[:int(lens[b])]
        vv = np.concatenate(
            [np.asarray(v_pages)[p] for p in np.asarray(tables)[b]],
            0)[:int(lens[b])]
        for h in range(H):
            s = kk[:, h] @ np.asarray(q)[b, h] / math.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            o[b, h] = p @ vv[:, h]
    np.testing.assert_allclose(np.asarray(out), o, rtol=2e-5, atol=2e-6)


# ragged rows, page 16, 4 table columns (max_len 64)
_PAGE, _COLS = 16, 4
_RAGGED = {
    "edges": [0, 1, _PAGE - 1, _PAGE, _PAGE + 1, _PAGE * _COLS],
    # the engine parks idle slots at length 1 between live ones
    "idle_between_live": [1, 50, 1, 1, _PAGE * _COLS, 1, 17, 1],
    "nothing_cached": [0, 0, 0],
}


def _ragged_pool(lens, kvh, d, rng):
    """A contiguous cache and the same tokens scattered over a pool whose
    table tails past each length hold out-of-range garbage; the two pages
    a clamped garbage entry would land on hold NaN."""
    b, max_len = len(lens), _PAGE * _COLS
    k = rng.rand(b, max_len, kvh, d).astype(np.float32)
    v = rng.rand(b, max_len, kvh, d).astype(np.float32)
    npages = b * _COLS + 2
    ids = rng.permutation(np.arange(1, npages - 1)).reshape(b, _COLS)
    k_pages = np.full((npages, _PAGE, kvh, d), np.nan, np.float32)
    v_pages = np.full((npages, _PAGE, kvh, d), np.nan, np.float32)
    k_pages[ids] = k.reshape(b, _COLS, _PAGE, kvh, d)
    v_pages[ids] = v.reshape(b, _COLS, _PAGE, kvh, d)
    live = np.arange(_COLS)[None, :] * _PAGE < np.asarray(lens)[:, None]
    garbage = np.where(np.arange(_COLS)[None, :] % 2, 10 ** 6, -7)
    tables = np.where(live, ids, garbage).astype(np.int32)
    return k, v, k_pages, v_pages, tables


@pytest.mark.parametrize("h,kvh", [(32, 8), (4, 4)], ids=["gqa32_8", "mha4"])
@pytest.mark.parametrize("case", sorted(_RAGGED))
def test_paged_attention_ragged_rows(case, h, kvh):
    lens = _RAGGED[case]
    rng = np.random.RandomState(len(lens) + h)
    d = 32
    k, v, k_pages, v_pages, tables = _ragged_pool(lens, kvh, d, rng)
    q = jnp.asarray(rng.rand(len(lens), h, d).astype(np.float32))
    lens = jnp.asarray(lens, jnp.int32)
    out = np.asarray(paged_attention(
        q, jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(tables),
        lens))
    # a row of length 0 yields zeros; no garbage entry was followed
    np.testing.assert_allclose(out, _ref_decode(q, k, v, lens),
                               rtol=2e-5, atol=2e-6)
    # the contiguous cache rides the same kernel on the same page walk
    np.testing.assert_array_equal(out, np.asarray(masked_decode_attention(
        q, jnp.asarray(k), jnp.asarray(v), lens, page_size=_PAGE)))


@pytest.mark.parametrize("case", sorted(_RAGGED))
def test_work_list_holds_the_live_pages_only(case):
    lens = np.asarray(_RAGGED[case], np.int32)
    tables = np.arange(lens.size * _COLS, dtype=np.int32).reshape(-1, _COLS)
    rows, pages, phys, total = _work_list(
        jnp.asarray(tables), jnp.asarray(lens), _PAGE, tables.size)
    # sum ceil(len / page), and one masked item for a row of length 0
    live = -(-lens // _PAGE)
    assert int(total) == live.sum() + (lens == 0).sum()
    want = [(r, c) for r, n in enumerate(np.maximum(live, 1))
            for c in range(n)]
    got = list(zip(np.asarray(rows)[:int(total)].tolist(),
                   np.asarray(pages)[:int(total)].tolist()))
    assert got == want
    np.testing.assert_array_equal(np.asarray(phys)[:int(total)],
                                  [tables[r, c] for r, c in want])
    assert rows.shape == pages.shape == phys.shape == (tables.size,)


def test_engine_counts_live_and_table_pages_per_dispatch():
    from paddle_tpu.core import telemetry
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    cfg = LlamaConfig(vocab_size=97, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=1, num_attention_heads=2,
                      max_position_embeddings=64, tie_word_embeddings=True)
    paddle.seed(0)
    eng = ContinuousBatchingEngine(LlamaForCausalLM(cfg), max_slots=4,
                                   max_len=64, page_size=16,
                                   prompt_buckets=(8,), seed=1)
    seen = []
    dispatch = eng._dispatch_segment

    def spy(*a, **kw):
        seen.append(eng._lengths.copy())
        return dispatch(*a, **kw)

    eng._dispatch_segment = spy
    live = telemetry.counter("serving.attn_pages_live_total")
    table = telemetry.counter("serving.attn_pages_table_total")
    live0, table0 = live.value(), table.value()
    rng = np.random.RandomState(2)
    out, _ = eng.run([rng.randint(0, 97, (n,)).astype(np.int32)
                      for n in (5, 30)], max_new_tokens=20, segment=4)
    assert len(out) == 2 and len(seen) >= 5
    assert all(len(x) == 4 for x in seen)
    held = [int((-(-x // 16)).sum()) for x in seen]
    assert live.value() - live0 == sum(held)
    # 4 slots x 4 columns a dispatch; an idle slot parks at one page
    assert table.value() - table0 == len(seen) * 4 * 4
    assert min(held) >= 4


def test_paged_cache_update_scatters_tokens():
    from paddle_tpu.models import PagedKVCache

    cache = PagedKVCache(batch=2, max_len=64, kv_heads=2, head_dim=8,
                         page_size=32)
    k = jnp.ones((2, 3, 2, 8))
    cache.update(k, 2 * k)
    assert cache.length == 3
    # pages are interleaved: page 0 of seq 0 is pool slot 0, seq 1 slot 1
    np.testing.assert_array_equal(np.asarray(cache.tables), [[0, 2], [1, 3]])
    assert float(cache.k_pages[0, 2, 0, 0]) == 1.0  # token 2 of seq 0
    assert float(cache.k_pages[1, 2, 0, 0]) == 1.0  # token 2 of seq 1
    assert float(cache.k_pages[0, 3, 0, 0]) == 0.0  # beyond length
    assert float(cache.v_pages[1, 1, 1, 3]) == 2.0


def _gen(cache_kind, flag_on):
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.models.generation import generate

    paddle.set_flags({"FLAGS_use_pallas_kernels": flag_on})
    try:
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny_config()).eval()
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 256, (2, 12)).astype(np.int32))
        out = generate(model, ids, max_new_tokens=6, cache=cache_kind)
        return np.asarray(out._value)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})


def test_generation_equivalent_across_cache_paths():
    """The Pallas decode kernels and cache layouts must not change tokens:
    static+kernel == static+jnp == paged+kernel."""
    base = _gen("static", False)   # masked jnp composition
    static_k = _gen("static", True)  # masked_decode_attention kernel
    paged_k = _gen("paged", True)    # paged_attention kernel
    np.testing.assert_array_equal(base, static_k)
    np.testing.assert_array_equal(base, paged_k)
