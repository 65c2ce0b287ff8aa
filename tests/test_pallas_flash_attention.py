"""Pallas flash attention vs the naive XLA sdpa composition.

Runs in interpreter mode on CPU (same code path the TPU compiles).
Mirrors the reference's flash_attn tests (test/legacy_test/test_flash_attention.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import flag
from paddle_tpu.ops.pallas import flash_attention as fa

from _jaxpr import pallas_names


def _naive(q, k, v, causal):
    """Plain attention; kv heads repeated over their group (GQA) and, for
    sq != sk, the causal diagonal shifted by the cache offset."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    kh, vh = (jnp.repeat(x, h // x.shape[1], axis=1) for x in (kh, vh))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_naive(causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 256, 4, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 256, 4, 64), jnp.float32)
    out = fa.flash_attention(q, k, v, is_causal=causal)
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal,group,sq,sk", [
    (False, 1, 128, 128), (True, 1, 128, 128),
    (False, 2, 128, 128), (True, 2, 128, 128),
    (False, 4, 256, 256), (True, 4, 256, 256),
    # sq != sk: the cache offset, with the 128 fallback on the q side
    # (384), on the k side (384) and 256 blocks on both
    (True, 2, 384, 512), (False, 2, 512, 384), (True, 4, 256, 512),
    (True, 1, 128, 384),
])
def test_backward_matches_naive(causal, group, sq, sk):
    """dq, dk, dv of the two backward kernels against plain attention's:
    MHA, grouped kv heads (a query head's dk / dv partial summed over the
    group), several k blocks a head and q blocks a k block."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, sq, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, sk, 4 // group, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, sk, 4 // group, 32), jnp.float32)

    def loss_fa(q, k, v):
        return (fa.flash_attention(q, k, v, is_causal=causal) ** 2).sum()

    def loss_naive(q, k, v):
        return (_naive(q, k, v, causal) ** 2).sum()

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_nv = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_fa, g_nv, "qkv"):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3, err_msg=n)


def test_backward_mixed_operand_dtypes():
    """float32 queries against a bfloat16 k / v (a cache's dtype): the dk/dv
    kernel feeds its score products the operands as they came only when
    they agree, and meets in float32 otherwise, as its siblings always do."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 256, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, 2, 32), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 256, 2, 32), jnp.bfloat16)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    g_fa = jax.grad(loss(lambda *a: fa.flash_attention(*a, is_causal=True)),
                    argnums=(0, 1, 2))(q, k, v)
    g_nv = jax.grad(loss(lambda q, k, v: _naive(
        q, k.astype(jnp.float32), v.astype(jnp.float32), True)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_fa, g_nv, "qkv"):
        assert a.dtype == b.dtype, n
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-2, rtol=2e-2, err_msg=n)


def test_gqa_repeat():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 128, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    out = fa.flash_attention(q, k, v, is_causal=True)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = _naive(q, kr, vr, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sdpa_routes_to_pallas():
    """The public op takes the Pallas path for qualifying shapes."""
    assert flag("FLAGS_use_pallas_kernels")
    q = paddle.to_tensor(np.random.rand(1, 128, 2, 32).astype(np.float32))
    out = paddle.scaled_dot_product_attention(q, q, q, is_causal=True)
    ref = _naive(q._value, q._value, q._value, True)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # unaligned seq falls back to the XLA path and still works
    q2 = paddle.to_tensor(np.random.rand(1, 100, 2, 32).astype(np.float32))
    out2 = paddle.scaled_dot_product_attention(q2, q2, q2, is_causal=True)
    assert out2.shape == [1, 100, 2, 32]


def test_grad_through_public_op():
    q = paddle.to_tensor(np.random.rand(1, 128, 2, 32).astype(np.float32),
                         stop_gradient=False)
    out = paddle.scaled_dot_product_attention(q, q, q, is_causal=True)
    out.sum().backward()
    assert q.grad is not None
    assert np.isfinite(np.asarray(q.grad._value)).all()


@pytest.mark.parametrize("sq,sk", [(256, 256), (512, 256), (256, 512),
                                   (384, 256)])
def test_mixed_block_sizes(sq, sk):
    """seqs hitting different preferred block sizes (256 vs 128) must stay
    exact, including the causal bounds."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, sq, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, sk, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, sk, 2, 32), jnp.float32)
    causal = sq <= sk  # causal cross shapes only valid when sk >= sq
    out = fa.flash_attention(q, k, v, is_causal=causal)

    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(32)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits.astype(jnp.float32), -1)
    ref = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _naive_masked(q, k, v, causal, seq_lens=None, segment_ids=None):
    """Oracle with -1e30 segment masking (matches kernel semantics)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q_seg, k_seg = fa.build_segments(b, sq, sk, seq_lens, segment_ids)
    qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -1e30)
    logits = jnp.where(q_seg[:, None, :, None] == k_seg[:, None, None, :],
                       logits, -1e30)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vh), 1, 2)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_seq_lens_padding(causal):
    """Per-sequence valid lengths (flash_attn varlen/padding analog,
    VERDICT r3 item 3): valid rows must match the masked oracle; padded-key
    columns must not leak into valid rows."""
    rng = np.random.RandomState(4)
    B, S, H, D = 2, 256, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    lens = jnp.asarray([200, 131], jnp.int32)
    out = fa.flash_attention(q, k, v, is_causal=causal, seq_lens=lens)
    ref = _naive_masked(q, k, v, causal, seq_lens=lens)
    for b in range(B):
        n = int(lens[b])
        np.testing.assert_allclose(np.asarray(out)[b, :n],
                                   np.asarray(ref)[b, :n],
                                   atol=2e-5, rtol=2e-5)


def test_forward_segment_ids_packed():
    """Packed sequences: tokens attend only within their own segment."""
    rng = np.random.RandomState(5)
    B, S, H, D = 1, 256, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    seg = jnp.asarray(
        np.concatenate([np.zeros(100), np.ones(90), np.full(66, 2)])[None, :],
        jnp.int32)
    out = fa.flash_attention(q, k, v, is_causal=True, segment_ids=seg)
    ref = _naive_masked(q, k, v, True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("group", [1, 2])
def test_backward_masked(group):
    """Grads through the masked kernel match the oracle on valid positions,
    and padded-key dk/dv are exactly zero (loss reads valid rows only);
    under GQA too, where every query head of the group masks its own
    partial."""
    rng = np.random.RandomState(6)
    B, S, H, D = 2, 128, 2, 32
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H // group, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H // group, D), jnp.float32)
    lens = jnp.asarray([128, 70], jnp.int32)
    valid = (jnp.arange(S)[None, :] < lens[:, None]).astype(jnp.float32)
    w = valid[:, :, None, None]

    def loss_fa(q, k, v):
        o = fa.flash_attention(q, k, v, is_causal=True, seq_lens=lens)
        return ((o * w) ** 2).sum()

    def loss_ref(q, k, v):
        o = _naive_masked(q, jnp.repeat(k, group, axis=2),
                          jnp.repeat(v, group, axis=2), True, seq_lens=lens)
        return ((o * w) ** 2).sum()

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_nv = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g_fa, g_nv, "qkv"):
        np.testing.assert_allclose(np.asarray(a) * (np.asarray(w) if n != "q" else 1.0),
                                   np.asarray(b) * (np.asarray(w) if n != "q" else 1.0),
                                   atol=1e-3, rtol=1e-3, err_msg=n)
    # padded keys must receive exactly zero gradient from the kernel
    assert np.abs(np.asarray(g_fa[1])[1, 70:]).max() == 0.0
    assert np.abs(np.asarray(g_fa[2])[1, 70:]).max() == 0.0


def test_sdpa_seq_lens_routes_and_fallback_warns():
    """The public op serves seq_lens through the kernel; a dense mask warns
    once and falls back."""
    assert flag("FLAGS_use_pallas_kernels")
    import warnings

    from paddle_tpu.ops import nn_kernels

    q = paddle.to_tensor(np.random.rand(2, 128, 2, 32).astype(np.float32))
    lens = paddle.to_tensor(np.asarray([128, 64], np.int32))
    out = paddle.scaled_dot_product_attention(q, q, q, is_causal=True,
                                              seq_lens=lens)
    ref = _naive_masked(q._value, q._value, q._value, True,
                        seq_lens=lens._value)
    np.testing.assert_allclose(np.asarray(out._value)[1, :64],
                               np.asarray(ref)[1, :64], atol=2e-5, rtol=2e-5)
    # dense-mask fallback warns exactly once
    nn_kernels._flash_fallback_warned.discard("dense attn_mask")
    mask = paddle.to_tensor(np.ones((1, 1, 128, 128), bool))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        paddle.scaled_dot_product_attention(q, q, q, attn_mask=mask)
        paddle.scaled_dot_product_attention(q, q, q, attn_mask=mask)
    msgs = [str(r.message) for r in rec if "flash-attention" in str(r.message)]
    assert len(msgs) == 1, msgs


def test_flash_attention_gqa_native():
    """GQA kv heads are used directly (no head materialization): forward
    and all three grads match the repeated-head reference exactly in
    interpret mode, including the grouped dk/dv accumulation."""
    import math

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    B, S, H, KVH, D = 2, 256, 8, 2, 64
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, KVH, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, KVH, D).astype(np.float32))

    def ref(q_, k_, v_):
        g = H // KVH
        kr = jnp.repeat(jnp.swapaxes(k_, 1, 2), g, axis=1)
        vr = jnp.repeat(jnp.swapaxes(v_, 1, 2), g, axis=1)
        qh = jnp.swapaxes(q_, 1, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kr) / math.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        return jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr), 1, 2)

    out = flash_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    loss = lambda fn: (lambda a, b, c: (fn(a, b, c) * jnp.arange(D)).sum())
    g1 = jax.grad(loss(lambda a, b, c: flash_attention(a, b, c, True)),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=n)
    # dk/dv keep the GROUPED shape: the memory win is structural
    assert g1[1].shape == (B, S, KVH, D)


def test_backward_custom_calls_keep_the_signature_the_benchmark_reads(
        monkeypatch):
    """``flash_bwd_roofline.train`` finds the two backward kernels by their
    operand list: four bf16 arrays, then ``lse`` and ``delta`` as f32 arrays
    whose last dimension is 1. Lowered for the TPU (nothing runs), at a GQA
    shape, both calls must still read so, and be the only two that do."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, S, H, KVH, D = 2, 512, 4, 2, 128

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, is_causal=True).astype(
            jnp.float32).sum()

    avals = [jax.ShapeDtypeStruct((B, S, n, D), jnp.bfloat16)
             for n in (H, KVH, KVH)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = {}
    for line in text.splitlines():
        m = re.search(r'@tpu_custom_call\(.*kernel_name = "(\w+)".* : '
                      r'\(([^)]*)\) ->', line)
        if m:
            calls[m.group(1)] = [t.strip() for t in m.group(2).split(", ")]
    assert set(calls) == {"flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq"}
    q_t, kv_t = f"tensor<{B}x{H}x{S}x{D}xbf16>", f"tensor<{B}x{KVH}x{S}x{D}xbf16>"
    col_t = f"tensor<{B}x{H}x{S}x1xf32>"
    for name in ("flash_bwd_dkdv", "flash_bwd_dq"):
        assert calls[name] == [q_t, kv_t, kv_t, q_t, col_t, col_t], name
    assert calls["flash_fwd"] == [q_t, kv_t, kv_t]


def test_build_segments_rejects_shared_ids_cross_attention():
    """One shared (B, S) segment_ids array only makes sense for self
    attention; a clear ValueError beats a shape mismatch deep in the
    kernel (advisor r4)."""
    import pytest

    from paddle_tpu.ops.pallas import flash_attention as fa

    ids = np.zeros((2, 16), np.int32)
    with pytest.raises(ValueError, match="sq == sk"):
        fa.build_segments(2, 16, 32, segment_ids=ids)
    # the pair form is the cross-attention spelling — accepted
    q_seg, k_seg = fa.build_segments(
        2, 16, 32, segment_ids=(np.zeros((2, 16), np.int32),
                                np.zeros((2, 32), np.int32)))
    assert q_seg.shape == (2, 16) and k_seg.shape == (2, 32)


def test_attention_op_traces_over_the_active_kernel_mesh():
    """Inside ``kernel_mesh`` the attention op — reached through the op
    surface and its per-op jit cache — traces to a ``shard_map`` over THAT
    scope's mesh, and to none outside a scope, whatever was traced before
    at the same shapes. (The cache keys on the scope; without that key a
    cross-mesh pipeline's later stages got the first stage's devices —
    tests/test_cross_mesh_pipeline.py fails.)"""
    import contextlib

    import jax
    from jax.sharding import Mesh

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.ops.pallas import kernel_mesh

    q = np.random.rand(2, 128, 4, 32).astype(np.float32)

    def shard_map_meshes(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "shard_map":
                found.append(eqn.params["mesh"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += shard_map_meshes(sub)
        return found

    def traced(scope):
        def f(x):
            with scope:
                t = Tensor._from_value(x)
                return paddle.scaled_dot_product_attention(
                    t, t, t, is_causal=True)._value
        return shard_map_meshes(jax.jit(f).trace(q).jaxpr.jaxpr)

    devs = np.array(jax.devices())
    mesh_a, mesh_b = Mesh(devs[:2], ("mp",)), Mesh(devs[2:4], ("mp",))
    in_a = traced(kernel_mesh(mesh_a, head_axis="mp"))
    plain = traced(contextlib.nullcontext())
    in_b = traced(kernel_mesh(mesh_b, head_axis="mp"))
    assert plain == []
    assert in_a and all(m.devices.tolist() == mesh_a.devices.tolist()
                        for m in in_a)
    assert in_b and all(m.devices.tolist() == mesh_b.devices.tolist()
                        for m in in_b)


# ------------------------------------------- forward over a paged cache

PAGE, PER_SEQ = 128, 4          # a row's table: 4 pages = max_len 512


def _prefill_over_cache(width, n_real, bases, heads, kv_heads, seed, d=16):
    """One layer's operands as the engine's chunk / final / resume
    programs hand them to ``cached_attention``: ``width`` rows of 128 new
    tokens, the last ``width - n_real`` of them padding rows (base 0, the
    table's scratch row), a pool whose live pages hold anything."""
    r = np.random.RandomState(seed)
    n_pages = n_real * PER_SEQ + 1                      # + the dump page
    real = r.permutation(n_pages - 1)[:n_real * PER_SEQ].reshape(
        n_real, PER_SEQ)
    tables = np.full((width, PER_SEQ + 1), n_pages - 1, np.int32)
    tables[:n_real, :PER_SEQ] = real
    offsets = np.zeros((width,), np.int32)
    offsets[:n_real] = bases
    q, k, v = (r.randn(width, 128, n, d).astype(np.float32)
               for n in (heads, kv_heads, kv_heads))
    pools = [r.randn(n_pages, PAGE, kv_heads, d).astype(np.float32)
             for _ in range(2)]
    return q, k, v, pools, tables, offsets


def _cached_attention(operands, kernels):
    """``models.llama.cached_attention`` over those operands, with the
    Pallas routes on or off (off: the masked composition, the oracle)."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.generation import _make_paged_cache
    from paddle_tpu.models.llama import cached_attention

    q, k, v, pools, tables, offsets = operands
    cache = _make_paged_cache(
        jnp.asarray(pools[0]), jnp.asarray(pools[1]), jnp.asarray(tables),
        PAGE, jnp.asarray(offsets), attn_pages=PER_SEQ)
    paddle.set_flags({"FLAGS_use_pallas_kernels": kernels})
    try:
        out = cached_attention(*(Tensor._from_value(jnp.asarray(x))
                                 for x in (q, k, v)), cache,
                               jnp.asarray(offsets), 128)
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})
    return np.asarray(out._value), cache


@pytest.mark.parametrize("heads,kv_heads", [(4, 1), (2, 2)],
                         ids=["gqa4to1", "mha"])
@pytest.mark.parametrize("width,n_real", [(1, 1), (4, 3)],
                         ids=["width1", "width4_padding_row"])
@pytest.mark.parametrize("bases", [
    (0, 0, 0), (128, 256, 384), (5, 200, 383), (0, 77, 256)],
    ids=["zero", "page_aligned", "mid_page", "per_row"])
def test_flash_attention_paged_matches_the_masked_composition(
        bases, width, n_real, heads, kv_heads):
    """Query ``r`` of row ``b`` sees cache columns ``<= base[b] + r``: the
    kernel over the row's pages against the composition that gathers the
    whole table and masks it, through the same ``cached_attention``."""
    operands = _prefill_over_cache(width, n_real, bases[:n_real], heads,
                                   kv_heads, seed=len(bases) + width)
    got, cache = _cached_attention(operands, kernels=True)
    want, oracle_cache = _cached_attention(operands, kernels=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # both wrote the chunk's keys before reading them
    np.testing.assert_array_equal(np.asarray(cache.k_pages),
                                  np.asarray(oracle_cache.k_pages))


@pytest.mark.parametrize("true_len", [1, 37])
def test_flash_attention_paged_final_chunk_tail_stays_finite(true_len):
    """A final chunk is padded to 128: its last real token may be the
    slot's last position, so the padded tail reaches past the table's
    attention-visible columns. Rows up to ``true_len`` are exact; the tail
    may hold anything finite."""
    base = PER_SEQ * PAGE - true_len
    operands = _prefill_over_cache(2, 1, (base,), 4, 2, seed=true_len)
    got, _ = _cached_attention(operands, kernels=True)
    want, _ = _cached_attention(operands, kernels=False)
    np.testing.assert_allclose(got[0, :true_len], want[0, :true_len],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-5)
    assert np.isfinite(got).all()


def test_cached_attention_routes_by_what_the_call_shows():
    """The route is chosen from the call alone: s > 1 behind an offset on
    a paged cache whose shapes the kernel takes. A scalar offset rides it
    too; a chunk the flash family declines (not a multiple of 128) and the
    kernels-off program keep the composition."""
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.generation import _make_paged_cache
    from paddle_tpu.models.llama import cached_attention

    q, k, v, pools, tables, offsets = _prefill_over_cache(
        2, 2, (128, 40), 4, 2, seed=0)

    def traced(offset, s, kernels=True):
        def f(q, k, v, kp, vp, offset):
            cache = _make_paged_cache(kp, vp, jnp.asarray(tables), PAGE,
                                      offset, attn_pages=PER_SEQ)
            return cached_attention(
                *(Tensor._from_value(x[:, :s]) for x in (q, k, v)), cache,
                offset, s)._value
        paddle.set_flags({"FLAGS_use_pallas_kernels": kernels})
        try:
            return pallas_names(jax.make_jaxpr(f)(
                q, k, v, *pools, offset).jaxpr)
        finally:
            paddle.set_flags({"FLAGS_use_pallas_kernels": True})

    assert traced(jnp.asarray(offsets), 128) == ["flash_fwd_paged"]
    assert traced(jnp.int32(128), 128) == ["flash_fwd_paged"]
    assert traced(jnp.asarray(offsets), 64) == []
    assert traced(jnp.asarray(offsets), 128, kernels=False) == []


def test_flash_attention_without_bases_traces_what_it_traced_before(
        monkeypatch):
    """The training cell's forward and backward kernels are the same
    family as the paged forward: a call that names no cache must trace to
    the program it traced before the paged kernel existed. The digests are
    of the jaxprs' text at commit 7ef5d9e (source positions and addresses
    taken out), recorded from that commit's own tree with this JAX. Since
    PR 32 the gradient also holds two ``name`` equations (the residuals a
    checkpoint policy may keep), which that commit had not: its digest is
    of the trace with the names taken out, and
    ``tests/test_recompute_flash_residuals.py`` holds that they lower to
    nothing."""
    import hashlib
    import re

    B, S, H, KVH, D = 2, 256, 4, 2, 128
    avals = [jax.ShapeDtypeStruct((B, S, n, D), jnp.bfloat16)
             for n in (H, KVH, KVH)]

    def forward(q, k, v):
        return fa.flash_attention(q, k, v, is_causal=True)

    def loss(q, k, v):
        return forward(q, k, v).astype(jnp.float32).sum()

    def digest(fn):
        jaxpr = jax.make_jaxpr(fn)(*avals)
        text = re.sub(r" at [^\n]*?:\d+", "", str(jaxpr))
        text = re.sub(r"\.py:\d+", ".py", text)
        text = re.sub(r"0x[0-9a-f]+", "0x", text)
        return jaxpr, hashlib.sha256(text.encode()).hexdigest()

    jaxpr, fwd = digest(forward)
    assert pallas_names(jaxpr.jaxpr) == ["flash_fwd"]
    assert fwd == ("f162a9bef57ec8787c7685f9f807a8783ef099a0"
                   "cf478bf2c5b9a0c8855db848")
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    _, bwd = digest(jax.grad(loss, argnums=(0, 1, 2)))
    assert bwd == ("9000b69575f67fac6cc927e1dd00aec6ca04bcab"
                   "0be96302faa8095620238f8d")
