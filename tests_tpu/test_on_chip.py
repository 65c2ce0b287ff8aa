"""On-chip (real TPU) test slice — guards against CPU-f32-only drift.

The main suite (tests/) forces a virtual CPU mesh for correctness CI;
nothing there ever exercises TPU-default bf16 matmuls or real Mosaic
lowering of the Pallas kernels (tests/test_tpu_aot_compile.py asks the
chip's compiler, but runs nothing). This slice runs ON THE CHIP, one
process holding it:

    python -m pytest tests_tpu/ -q          # skipped without a TPU

Covered: bf16 matmul numerics, op spot-checks at bf16 tolerances, the
Pallas kernels (flash attention fwd+bwd, RMSNorm, paged/masked decode
attention, fused rope, fused bias-dropout-residual-LN), one compiled
TrainStep and the continuous-batching engine. A kernel in interpret mode
or a native library that cannot be built is a failure here, not a warning.
Pass/fail per test is recorded in CHANGES.md by the PR that ran it.
"""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

if jax.default_backend() != "tpu":  # pragma: no cover
    pytest.skip("tests_tpu/ requires a real TPU backend",
                allow_module_level=True)

rng = np.random.RandomState(0)

# bf16 has ~3 decimal digits; matmul accumulates in f32 on the MXU
BF16_RTOL = 2e-2
BF16_ATOL = 2e-2


def test_no_hidden_fallback_on_chip():
    """What the CPU suite is allowed to do quietly is a failure here."""
    from paddle_tpu import native
    from paddle_tpu.ops import pallas

    assert not pallas.interpret()
    for name in ("tcp_store", "token_reader"):
        assert native.load_library(name) is not None, name


def test_bf16_matmul_against_f32():
    a = rng.rand(256, 512).astype(np.float32)
    b = rng.rand(512, 128).astype(np.float32)
    out = jax.jit(jnp.matmul)(jnp.asarray(a, jnp.bfloat16),
                              jnp.asarray(b, jnp.bfloat16))
    np.testing.assert_allclose(np.asarray(out, np.float32), a @ b,
                               rtol=BF16_RTOL, atol=BF16_ATOL * 128)


def test_op_spot_checks_bf16():
    import paddle_tpu as paddle
    import paddle_tpu.ops as ops

    x = rng.rand(64, 128).astype(np.float32)
    # softmax — exp/renorm on VPU
    got = np.asarray(ops.softmax(paddle.to_tensor(x))._value)
    e = np.exp(x - x.max(-1, keepdims=True))
    np.testing.assert_allclose(got, e / e.sum(-1, keepdims=True),
                               rtol=1e-4, atol=1e-5)
    # layer_norm
    g = rng.rand(128).astype(np.float32)
    b = rng.rand(128).astype(np.float32)
    got = np.asarray(ops.layer_norm(paddle.to_tensor(x), paddle.to_tensor(g),
                                    paddle.to_tensor(b))._value)
    m = x.mean(-1, keepdims=True)
    v = x.var(-1, keepdims=True)
    np.testing.assert_allclose(got, (x - m) / np.sqrt(v + 1e-5) * g + b,
                               rtol=1e-3, atol=1e-3)
    # logsumexp numerics at bf16 inputs
    xb = paddle.to_tensor(np.asarray(x, np.float32)).astype("bfloat16")
    got = np.asarray(ops.logsumexp(xb, axis=-1)._value, np.float32)
    ref = np.log(np.exp(x - x.max(-1, keepdims=True)).sum(-1)) + x.max(-1)
    np.testing.assert_allclose(got, ref, rtol=BF16_RTOL, atol=BF16_ATOL)


def test_pallas_flash_attention_on_chip():
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, D = 2, 256, 4, 128
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))

    hi = jax.lax.Precision.HIGHEST  # match the kernel's f32 accumulation

    def ref(q, k, v):
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh, precision=hi) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        return jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vh,
                       precision=hi), 1, 2)

    out = flash_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    # backward on-chip. Early causal rows cancel catastrophically in
    # (dp - delta) — their grads are ~1e-2 with ~5e-3 f32 noise on both
    # sides — so this is a lowering sanity check at loose tolerance; the
    # exact-math check runs in interpret mode (tests/test_pallas_*).
    g1 = jax.grad(lambda q_: flash_attention(q_, k, v, True).sum())(q)
    g2 = jax.grad(lambda q_: ref(q_, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-2,
                               atol=1e-2)


def test_pallas_rms_norm_on_chip():
    import paddle_tpu as paddle
    from paddle_tpu.ops import rms_norm

    x = rng.rand(8, 64, 512).astype(np.float32)
    w = rng.rand(512).astype(np.float32)
    got = np.asarray(rms_norm(paddle.to_tensor(x),
                              paddle.to_tensor(w))._value)
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


def test_pallas_decode_kernels_on_chip():
    from paddle_tpu.ops.pallas.decode_attention import (
        masked_decode_attention, paged_attention)

    B, H, KVH, D, L = 2, 8, 4, 128, 256
    q = jnp.asarray(rng.rand(B, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, L, KVH, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, L, KVH, D).astype(np.float32))
    lens = jnp.asarray([100, 256], jnp.int32)
    out = masked_decode_attention(q, k, v, lens)
    g = H // KVH
    for b in range(B):
        for h in range(H):
            kk = np.asarray(k)[b, :int(lens[b]), h // g]
            vv = np.asarray(v)[b, :int(lens[b]), h // g]
            s = kk @ np.asarray(q)[b, h] / math.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(np.asarray(out)[b, h], p @ vv,
                                       rtol=2e-3, atol=2e-4)

    # paged with scattered tables (scalar-prefetch index maps on Mosaic)
    PAGE, NPAGES = 128, 16
    k_pages = jnp.asarray(rng.rand(NPAGES, PAGE, KVH, D).astype(np.float32))
    v_pages = jnp.asarray(rng.rand(NPAGES, PAGE, KVH, D).astype(np.float32))
    tables = jnp.asarray(rng.permutation(NPAGES).reshape(B, 8), jnp.int32)
    plens = jnp.asarray([900, 520], jnp.int32)
    pout = paged_attention(q, k_pages, v_pages, tables, plens)
    for b in range(B):
        kk = np.concatenate([np.asarray(k_pages)[p_]
                             for p_ in np.asarray(tables)[b]],
                            0)[:int(plens[b])]
        vv = np.concatenate([np.asarray(v_pages)[p_]
                             for p_ in np.asarray(tables)[b]],
                            0)[:int(plens[b])]
        for h in range(H):
            s = kk[:, h // g] @ np.asarray(q)[b, h] / math.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(np.asarray(pout)[b, h],
                                       p @ vv[:, h // g],
                                       rtol=2e-3, atol=2e-4)


def test_pallas_fused_rope_and_bdrln_on_chip():
    from paddle_tpu.ops.pallas.fused_ops import (
        bias_dropout_residual_ln, fused_rope)

    B, S, H, D = 2, 64, 8, 128
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    inv = 1.0 / (10000 ** (np.arange(0, D, 2) / D))
    fr = np.outer(np.arange(S), inv)
    emb = np.concatenate([fr, fr], -1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)
    sin = jnp.asarray(np.sin(emb), jnp.float32)
    oq, _ = fused_rope(q, None, cos, sin)
    half = D // 2
    rot = jnp.concatenate([-q[..., half:], q[..., :half]], -1)
    ref = q * cos[None, :, None, :] + rot * sin[None, :, None, :]
    np.testing.assert_allclose(np.asarray(oq), np.asarray(ref), rtol=2e-3,
                               atol=2e-4)

    x = jnp.asarray(rng.rand(4, 64, 512).astype(np.float32))
    res = jnp.asarray(rng.rand(4, 64, 512).astype(np.float32))
    y = bias_dropout_residual_ln(x, res, dropout_rate=0.0, training=False)
    z = x + res
    m = z.mean(-1, keepdims=True)
    v = ((z - m) ** 2).mean(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray((z - m) / jnp.sqrt(v + 1e-5)),
                               rtol=2e-3, atol=2e-3)


def test_train_step_on_chip():
    import paddle_tpu as paddle
    from paddle_tpu.models import (LlamaForCausalLM,
                                   LlamaPretrainingCriterion,
                                   llama_tiny_config)

    paddle.seed(0)
    cfg = llama_tiny_config(hidden_size=256, num_hidden_layers=2,
                            num_attention_heads=8, vocab_size=512,
                            max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (4, 128)).astype(np.int32))
    step = paddle.jit.TrainStep(model, lambda logits: crit(logits, ids), opt)
    losses = [float(step(ids)) for _ in range(4)]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_pallas_flash_attention_gqa_on_chip():
    """GQA index maps + grouped dk/dv revisit-accumulation must lower
    through Mosaic; numerics checked norm-relative (the sum() cotangent
    cancels heavily in f32, so elementwise tolerance is the wrong bar —
    interpret mode holds the exact-math contract)."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, KVH, D = 2, 256, 8, 2, 128
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, KVH, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, KVH, D).astype(np.float32))
    hi = jax.lax.Precision.HIGHEST

    def ref(q_, k_, v_):
        g = H // KVH
        kr = jnp.repeat(jnp.swapaxes(k_, 1, 2), g, axis=1)
        vr = jnp.repeat(jnp.swapaxes(v_, 1, 2), g, axis=1)
        qh = jnp.swapaxes(q_, 1, 2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kr,
                       precision=hi) / math.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        return jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vr,
                       precision=hi), 1, 2)

    out = flash_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-3, atol=2e-3)
    g1 = np.asarray(jax.grad(
        lambda k_: flash_attention(q, k_, v, True).sum())(k))
    g2 = np.asarray(jax.grad(lambda k_: ref(q, k_, v).sum())(k))
    rel = np.linalg.norm(g1 - g2) / np.linalg.norm(g2)
    assert rel < 1e-2, rel


def test_pallas_flash_attention_gqa_backward_at_the_train_cell_shape():
    """The backward at the shape ``internlm2-d12-pretrain-1chip`` runs twelve
    times a step: batch 2, 4096 tokens, 16 query / 8 KV heads of 128,
    causal, bfloat16. dq, dk, dv against plain attention in float32 at
    ``highest``, a batch row at a time (its scores are 1 GiB), by the norm
    of the difference: the kernels' products round their operands to
    bfloat16 (2^-9), the oracle does not."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    B, S, H, KVH, D = 2, 4096, 16, 8, 128
    keys = jax.random.split(jax.random.PRNGKey(28), 4)
    q, k, v, w = (jax.random.normal(key, (B, S, n, D), jnp.bfloat16)
                  for key, n in zip(keys, (H, KVH, KVH, H)))
    hi = jax.lax.Precision.HIGHEST

    def ref(q_, k_, v_):
        qh, kh, vh = (jnp.swapaxes(x.astype(jnp.float32), 1, 2)
                      for x in (q_, k_, v_))
        kh, vh = (jnp.repeat(x, H // KVH, axis=1) for x in (kh, vh))
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                       precision=hi) / math.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        return jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vh,
                       precision=hi), 1, 2)

    def loss(fn):
        return lambda q_, k_, v_, w_: (
            fn(q_, k_, v_).astype(jnp.float32) * w_.astype(jnp.float32)).sum()

    got = jax.jit(jax.grad(
        loss(lambda *a: flash_attention(*a, is_causal=True)),
        argnums=(0, 1, 2)))(q, k, v, w)
    ref_grad = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))
    for row in range(B):
        one = slice(row, row + 1)
        want = ref_grad(q[one], k[one], v[one], w[one])
        for a, b, n in zip(got, want, ("dq", "dk", "dv")):
            assert a.shape[1:] == b.shape[1:] and a.dtype == jnp.bfloat16, n
            a = np.asarray(a[one], np.float32)
            b = np.asarray(b, np.float32)
            rel = np.linalg.norm(a - b) / np.linalg.norm(b)
            assert rel < 1e-2, (n, row, rel)


def test_recompute_keeps_flash_residuals_at_the_train_cell_widths(capsys):
    """Two layers at ``internlm2-d12-pretrain-1chip``'s widths (hidden 2048,
    16 / 8 heads of 128, SwiGLU 8192, 2 x 4096 tokens, bfloat16, fused
    lm-head + CE; a vocabulary of 8,192 keeps the oracle small). The
    recomputed gradient program keeps the five arrays the flash backward
    reads (q, k, v, ``out``, ``lse``), runs ``flash_fwd`` once a layer and
    ``fused_rope`` four times (no rope in the recomputation); its loss and
    every gradient have to be those of the program with no recompute, both
    against the same weights in float32 at ``highest`` with every Pallas
    route off, a batch row at a time (a row's scores are 1 GiB a layer). By
    the norm of the difference over the oracle's norm, a leaf at a time."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.jit import _FunctionalModel
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    layers, batch, seq = 2, 2, 4096
    paddle.seed(32)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=8192, hidden_size=2048, intermediate_size=8192,
        num_hidden_layers=layers, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=seq, rope_theta=1e6))
    functional = _FunctionalModel(model)
    p16 = {k: p._value.astype(jnp.bfloat16)
           for k, p in model.named_parameters()}
    p32 = {k: v.astype(jnp.float32) for k, v in p16.items()}
    buffers = {k: b._value for k, b in model.named_buffers()}
    key = jax.random.key_data(jax.random.key(0))
    ids = jnp.asarray(rng.randint(0, 8192, (batch, seq)), jnp.int32)

    def loss(params, ids):
        return functional(params, buffers, (ids,), {"labels": ids}, key)[0]

    got = {}
    for rc in (False, True):
        model.config.use_recompute = rc
        step = jax.jit(jax.value_and_grad(loss))
        if rc:
            text = step.lower(p16, ids).compile().as_text()
            for kernel, a_layer in (("flash_fwd", 1), ("flash_bwd_dq", 1),
                                    ("flash_bwd_dkdv", 1),
                                    ("fused_rope", 4)):
                calls = re.findall(
                    rf"^\s*%?{kernel}(?:\.\d+)? = .*tpu_custom_call", text,
                    re.M)
                assert len(calls) == a_layer * layers, (kernel, len(calls))
        got[rc] = jax.block_until_ready(step(p16, ids))

    model.config.use_recompute = False
    paddle.set_flags({"FLAGS_use_pallas_kernels": False})
    try:
        with jax.default_matmul_precision("highest"):
            oracle = jax.jit(jax.value_and_grad(loss))
            rows = [oracle(p32, ids[r:r + 1]) for r in range(batch)]
    finally:
        paddle.set_flags({"FLAGS_use_pallas_kernels": True})
    want_loss = float(sum(r[0] for r in rows)) / batch
    want = {k: sum(np.asarray(r[1][k], np.float32) for r in rows) / batch
            for k in p32}

    def f32(grads):
        return {k: np.asarray(v, np.float32) for k, v in grads.items()}

    def worst_leaf(a, b):
        return max(float(np.linalg.norm(a[k] - b[k])
                         / np.linalg.norm(want[k])) for k in want)

    plain, kept = f32(got[False][1]), f32(got[True][1])
    worst = {"recompute - oracle": worst_leaf(kept, want),
             "plain - oracle": worst_leaf(plain, want),
             "recompute - plain": worst_leaf(kept, plain)}
    with capsys.disabled():
        print(f"\nrecompute at the train cell's widths: loss "
              f"{float(got[True][0]):.6f} (plain {float(got[False][0]):.6f},"
              f" float32 {want_loss:.6f}); worst leaf {worst}")
    assert abs(float(got[True][0]) - float(got[False][0])) < 1e-3
    assert abs(float(got[True][0]) - want_loss) < 2e-2
    assert worst["recompute - oracle"] < 3e-2, worst
    # the two bfloat16 programs stand closer to each other than to float32
    assert worst["recompute - plain"] <= worst["plain - oracle"], worst


def test_pallas_flash_attention_masked_on_chip():
    """seq_lens padding + segment-id masking must lower through Mosaic
    ((1, S) int32 seg blocks in all three kernels) and match the masked
    oracle on valid rows, fwd + dq/dk."""
    from paddle_tpu.ops.pallas.flash_attention import (
        build_segments, flash_attention,
    )

    B, S, H, D = 2, 256, 4, 128
    q = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    k = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.rand(B, S, H, D).astype(np.float32))
    lens = jnp.asarray([256, 140], jnp.int32)
    seg = jnp.asarray(
        np.concatenate([np.zeros(128), np.ones(128)])[None, :].repeat(B, 0),
        jnp.int32)
    hi = jax.lax.Precision.HIGHEST

    def ref(q_, k_, v_):
        q_seg, k_seg = build_segments(B, S, S, lens, seg)
        qh, kh, vh = (jnp.swapaxes(x, 1, 2) for x in (q_, k_, v_))
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                       precision=hi) / math.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
        s = jnp.where(q_seg[:, None, :, None] == k_seg[:, None, None, :],
                      s, -1e30)
        return jnp.swapaxes(
            jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vh,
                       precision=hi), 1, 2)

    valid = (jnp.arange(S)[None, :] < lens[:, None]).astype(
        jnp.float32)[:, :, None, None]
    out = flash_attention(q, k, v, is_causal=True, seq_lens=lens,
                          segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out * valid),
                               np.asarray(ref(q, k, v) * valid),
                               rtol=2e-3, atol=2e-3)
    # bwd: elementwise at the f32-cancellation noise floor (~1e-2, same as
    # the unmasked on-chip bwd check); interpret mode holds exact math
    loss = lambda fn: (lambda a: ((fn(a) * valid) ** 2).sum())
    gq1 = np.asarray(jax.grad(loss(
        lambda q_: flash_attention(q_, k, v, True, lens, seg)))(q))
    gq2 = np.asarray(jax.grad(loss(lambda q_: ref(q_, k, v)))(q))
    np.testing.assert_allclose(gq1, gq2, atol=2e-2, rtol=2e-2)
    gk1 = np.asarray(jax.grad(loss(
        lambda k_: flash_attention(q, k_, v, True, lens, seg)))(k))
    gk2 = np.asarray(jax.grad(loss(lambda k_: ref(q, k_, v)))(k))
    np.testing.assert_allclose(gk1, gk2, atol=2e-2, rtol=2e-2)
    # padded keys get exactly zero grad from the kernel
    assert np.abs(gk1[1, 140:]).max() == 0.0


def test_fused_linear_cross_entropy_on_chip():
    """Round-5 fused lm-head+CE: bf16 operands, f32 online-softmax
    accumulation, fwd + grads vs the unfused composition ON the chip."""
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy as flce

    N, H, V = 128, 256, 2048
    x = jnp.asarray(rng.standard_normal((N, H)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((V, H)) * 0.1, jnp.bfloat16)
    lab = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)

    def dense(x, w):
        logits = jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, lab[:, None], 1)[:, 0]

    got = jax.jit(lambda x, w: flce(x, w, lab, block_size=512))(x, w)
    want = jax.jit(dense)(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)

    gf = jax.jit(jax.grad(lambda x, w: flce(x, w, lab).mean(),
                          argnums=(0, 1)))(x, w)
    gr = jax.jit(jax.grad(lambda x, w: dense(x, w).mean(),
                          argnums=(0, 1)))(x, w)
    np.testing.assert_allclose(np.asarray(gf[0], np.float32),
                               np.asarray(gr[0], np.float32),
                               rtol=BF16_RTOL, atol=BF16_ATOL)
    np.testing.assert_allclose(np.asarray(gf[1], np.float32),
                               np.asarray(gr[1], np.float32),
                               rtol=BF16_RTOL, atol=BF16_ATOL)

    # InternLM2's untied (H, V) head (the training cell's: hidden 2048,
    # vocabulary 92,544, 23 blocks of 4,096, the last padded) on a slice of
    # tokens: bf16 dx / dW against the float32 dense gradient of the same
    # bf16 values. The one-hot rides the MXU with softmax * g, so the
    # label's entry is rounded as the rest of the block's (PERF.md §6).
    N, H, V = 1024, 2048, 92544
    x = jnp.asarray(rng.standard_normal((N, H)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.02, jnp.bfloat16)
    lab = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)

    def dense32(x, w):
        logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, lab[:, None], 1)[:, 0]

    gf = jax.jit(jax.grad(lambda x, w: flce(x, w, lab,
                                            transpose_y=False).mean(),
                          argnums=(0, 1)))(x, w)
    gr = jax.jit(jax.grad(lambda x, w: dense32(x, w).mean(),
                          argnums=(0, 1)))(x.astype(jnp.float32),
                                           w.astype(jnp.float32))
    assert gf[0].dtype == gf[1].dtype == jnp.bfloat16
    gaps = {}
    for name, got, want in (("dx", gf[0], gr[0]), ("dW", gf[1], gr[1])):
        got = np.asarray(got, np.float32)
        want = np.asarray(want)
        gaps[name] = {
            "norm": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
            "max": float(np.abs(got - want).max() / np.abs(want).max())}
    print("FUSED_CE_HEAD_GAPS", gaps)
    for name, gap in gaps.items():
        assert gap["norm"] < 1e-2 and gap["max"] < 1e-2, (name, gap)


def test_fused_linear_cross_entropy_mean_path_on_chip():
    """The mean path (``reduction="mean"``, the training criterion's): the
    whole gradient formed in one walk over row chunks. InternLM2's untied
    (H, V) head at the training cell's widths on 2 x 2,050 tokens (three
    chunks of 2 x 688, each sequence padded by 14 rows), a few rows at
    ignore_index: loss, bf16 dx and dW against the float32 dense gradient
    of the same bf16 values and against the per-token path's .mean()
    (PERF.md §6)."""
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy as flce

    B, T, H, V = 2, 2050, 2048, 92544
    x = jnp.asarray(rng.standard_normal((B, T, H)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((H, V)) * 0.02, jnp.bfloat16)
    lab_np = rng.randint(0, V, (B * T,))
    lab_np[[3, 2050, B * T - 1]] = -100
    lab = jnp.asarray(lab_np.reshape(B, T), jnp.int32)

    def dense32(x, w):
        logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
        logp = jax.nn.log_softmax(logits, -1)
        safe = jnp.where(lab == -100, 0, lab)
        loss = -jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
        return jnp.where(lab == -100, 0.0, loss).mean()

    lm, gm = jax.jit(jax.value_and_grad(
        lambda x, w: flce(x, w, lab, transpose_y=False, reduction="mean"),
        argnums=(0, 1)))(x, w)
    lt, gt = jax.jit(jax.value_and_grad(
        lambda x, w: flce(x, w, lab, transpose_y=False).mean(),
        argnums=(0, 1)))(x, w)
    lr, gr = jax.jit(jax.value_and_grad(dense32, argnums=(0, 1)))(
        x.astype(jnp.float32), w.astype(jnp.float32))
    assert gm[0].dtype == gm[1].dtype == jnp.bfloat16
    gaps = {"loss": float(abs(lm - lr) / abs(lr)),
            "loss_vs_per_token": float(abs(lm - lt) / abs(lt))}
    for name, got, want in (("dx", gm[0], gr[0]), ("dW", gm[1], gr[1]),
                            ("dx_vs_per_token", gm[0], gt[0]),
                            ("dW_vs_per_token", gm[1], gt[1])):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        gaps[name] = {
            "norm": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
            "max": float(np.abs(got - want).max() / np.abs(want).max())}
    print("FUSED_CE_MEAN_GAPS", gaps)
    assert gaps["loss"] < 1e-4 and gaps["loss_vs_per_token"] < 1e-4, gaps
    for name in ("dx", "dW", "dx_vs_per_token", "dW_vs_per_token"):
        assert gaps[name]["norm"] < 1e-2 and gaps[name]["max"] < 1e-2, (
            name, gaps[name])


def test_continuous_batching_on_chip():
    """Per-slot-depth decode segments (continuous batching) must emit the
    same greedy tokens as per-request generate() with the REAL paged
    Pallas kernel in the loop."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.generation import generate
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    cfg = LlamaConfig(vocab_size=512, hidden_size=256,
                      intermediate_size=512, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2,
                      max_position_embeddings=512,
                      tie_word_embeddings=True)
    paddle.seed(0)
    m = LlamaForCausalLM(cfg)
    m.to(dtype="bfloat16")
    prompts = [rng.randint(0, 512, (n,)).astype(np.int32)
               for n in (7, 19, 12)]
    eng = ContinuousBatchingEngine(m, max_slots=2, max_len=256,
                                   page_size=128, prompt_buckets=(32,))
    outs, stats = eng.run(prompts, max_new_tokens=8, segment=4)
    assert stats["useful_tokens"] == 3 * 8
    for i, p in enumerate(prompts):
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=8,
                     cache="paged")._value)[0, p.size:]
        np.testing.assert_array_equal(outs[i], want, err_msg=f"req {i}")


def test_paged_mla_attention_on_chip():
    """The latent decode kernel at the JoyAI widths (32 heads, latent 512,
    rope 64, page 128), bf16 pools, scattered tables and ragged lengths,
    against its jnp oracle computed in float32."""
    from paddle_tpu.ops.pallas.mla_attention import (
        mla_attention_reference, paged_mla_attention)

    B, H, C, R, PAGE, NPAGES = 4, 32, 512, 64, 128, 24
    bf = jnp.bfloat16
    ql = jnp.asarray(rng.randn(B, H, C).astype(np.float32) * 0.1, bf)
    qr = jnp.asarray(rng.randn(B, H, R).astype(np.float32) * 0.1, bf)
    cp = jnp.asarray(rng.randn(NPAGES, PAGE, C).astype(np.float32), bf)
    rp = jnp.asarray(rng.randn(NPAGES, PAGE, R).astype(np.float32), bf)
    tables = jnp.asarray(rng.permutation(NPAGES).reshape(B, 6), jnp.int32)
    lens = jnp.asarray([1, 700, 129, 0], jnp.int32)
    scale = 1.0 / math.sqrt(192)
    out = paged_mla_attention(ql, qr, cp, rp, tables, lens, scale)
    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        want = mla_attention_reference(ql.astype(f32), qr.astype(f32),
                                       cp.astype(f32), rp.astype(f32),
                                       tables, lens, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=BF16_RTOL,
                               atol=BF16_ATOL)


@pytest.mark.parametrize("m,total", [(256, 152), (4096, 4000)])
def test_moe_gmm_on_chip(m, total):
    """The grouped product at the JoyAI expert shapes (K 2048, N 1536,
    bf16) over 64 experts, most of them empty at the decode size, against
    its oracle in float32."""
    from paddle_tpu.ops.pallas.moe_gmm import gmm_reference, moe_gmm

    E, K, N = 64, 2048, 1536
    sizes = np.zeros(E, np.int32)
    hit = rng.choice(E, 29, replace=False)
    sizes[hit] = rng.multinomial(total - 29, [1 / 29] * 29) + 1
    lhs = jnp.asarray(rng.randn(m, K).astype(np.float32), jnp.bfloat16)
    rhs = jnp.asarray(rng.randn(E, K, N).astype(np.float32) * 0.02,
                      jnp.bfloat16)
    out = moe_gmm(lhs, rhs, jnp.asarray(sizes))
    with jax.default_matmul_precision("highest"):
        want = gmm_reference(lhs.astype(jnp.float32),
                             rhs.astype(jnp.float32), jnp.asarray(sizes))
    np.testing.assert_allclose(np.asarray(out, np.float32)[:total],
                               np.asarray(want)[:total], rtol=BF16_RTOL,
                               atol=BF16_ATOL)


def _prefill_over_cache_operands(width, bases, seed):
    """The chat cell's chunk program at one layer: 32 query / 8 kv heads
    of 128, page 128, ``max_len`` 4096, a 513-page bf16 pool."""
    r = np.random.RandomState(seed)
    H, KVH, D, PAGE, PER, NPAGES, S = 32, 8, 128, 128, 32, 513, 128
    bf = jnp.bfloat16
    q = jnp.asarray(r.randn(width, S, H, D).astype(np.float32), bf)
    kp = jnp.asarray(r.randn(NPAGES, PAGE, KVH, D).astype(np.float32), bf)
    vp = jnp.asarray(r.randn(NPAGES, PAGE, KVH, D).astype(np.float32), bf)
    tables = jnp.asarray(
        r.permutation(NPAGES - 1)[:width * PER].reshape(width, PER),
        jnp.int32)
    return q, kp, vp, tables, jnp.asarray(bases, jnp.int32)


def _masked_composition(q, kp, vp, tables, bases):
    """What ``cached_attention`` ran for a chunk before the kernel: every
    table column gathered, every column scored, a dense mask."""
    from paddle_tpu.ops.nn_kernels import scaled_dot_product_attention

    b, s = q.shape[0], q.shape[1]
    k = kp[tables].reshape(b, -1, *kp.shape[2:])
    v = vp[tables].reshape(b, -1, *vp.shape[2:])
    rows = jnp.arange(s)[None, :] + bases[:, None]
    mask = (jnp.arange(k.shape[1])[None, None, None, :]
            <= rows[:, None, :, None])
    return scaled_dot_product_attention(q, k, v, attn_mask=mask)


def _device_ms_a_call(fn, q, *rest, steps=25):
    """Mean time of one ``fn(q, *rest)`` inside ONE program that calls it
    ``steps`` times, each call's output the next one's queries (a host
    that times single calls reads its own dispatch, ~0.2 ms)."""
    import time

    @jax.jit
    def many(q, *rest):
        return jax.lax.scan(lambda q, _: (fn(q, *rest), None), q, None,
                            length=steps)[0]

    many(q, *rest).block_until_ready()
    t0 = time.perf_counter()
    many(q, *rest).block_until_ready()
    return 1e3 * (time.perf_counter() - t0) / steps


@pytest.mark.parametrize("width,bases", [
    (1, [0]), (1, [128]), (1, [1000]), (2, [0, 1000]), (2, [128, 1000]),
])
def test_flash_attention_paged_at_the_chat_cell_shape(width, bases):
    """Prefill over a cache, kernel against the masked composition it
    replaced, at the shapes ``mistral7b-chat-open`` runs ``chunk_step``
    / ``final_chunk`` with."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_paged

    ops = _prefill_over_cache_operands(width, bases, 2147490000 % 2**31)
    out = jax.jit(flash_attention_paged)(*ops)
    want = jax.jit(_masked_composition)(*ops)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=BF16_RTOL, atol=BF16_ATOL)


def test_flash_attention_paged_time_follows_the_base(capsys):
    """A chunk at base 128 looks at 2 pages, one at base 3,968 at 32: the
    kernel's time has to follow that, and the composition's does not."""
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_paged

    kernel = jax.jit(flash_attention_paged)
    composition = jax.jit(_masked_composition)
    ms = {}
    for base in (0, 128, 1000, 3968):
        ops = _prefill_over_cache_operands(1, [base], 7)
        ms[base] = (_device_ms_a_call(kernel, *ops),
                    _device_ms_a_call(composition, *ops))
    with capsys.disabled():
        for base, (k_ms, c_ms) in ms.items():
            print(f"\nflash_fwd_paged width 1 base {base}: kernel "
                  f"{k_ms:.4f} ms a call, masked composition {c_ms:.4f}")
    assert ms[128][0] < 0.5 * ms[3968][0]
    assert ms[128][0] < ms[128][1]


def _retention_operands(b, n, slots, seed):
    """The continue cell's layer: 40 query heads over 8 kv heads of 128,
    bf16 activations, the float32 state of ``slots`` slots made by a first
    chunk so that it is one a sequence could hold."""
    from paddle_tpu.ops.pallas import retention as R

    r = np.random.RandomState(seed)
    H, KV, D = 40, 8, 128
    bf = jnp.bfloat16
    q = jnp.asarray(r.randn(b, n, H, D).astype(np.float32), bf)
    k = jnp.asarray(r.randn(b, n, KV, D).astype(np.float32) / 11.3, bf)
    v = jnp.asarray(r.randn(b, n, KV, D).astype(np.float32), bf)
    logg = jax.nn.log_sigmoid(jnp.asarray(
        4.0 + 4.0 * r.rand(b, n, KV).astype(np.float32)))
    s_shape, z_shape = R.state_shapes(KV, D)
    rows = jnp.asarray(r.permutation(slots)[:b], jnp.int32)
    return q, k, v, logg, jnp.zeros((slots,) + s_shape), \
        jnp.zeros((slots,) + z_shape), rows


def test_power_retention_kernels_at_the_continue_cell_widths(capsys):
    """A chunk of 128 from the zero state, a second on the state it left,
    then a decode step over a work list with a dead row: each kernel
    against its jnp form in float32, the dead row's state untouched, and
    the decode kernel's time a call a live row printed beside its bytes'
    floor (2 x 34.08 MB at 819 GB/s = 83 us)."""
    from paddle_tpu.ops.pallas import retention as R

    q, k, v, logg, s, z, rows = _retention_operands(3, 256, 5, 11)
    f32 = jnp.float32
    chunk = jax.jit(R.power_retention_chunk)
    oracle = jax.jit(R.retention_chunk_reference)
    for sl in (slice(0, 128), slice(128, 256)):
        args = (q[:, sl], k[:, sl], v[:, sl], logg[:, sl])
        y, s1, z1 = chunk(*args, s, z, rows)
        with jax.default_matmul_precision("highest"):
            want, s0, z0 = oracle(*(a.astype(f32) for a in args), s, z, rows)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(want), rtol=BF16_RTOL,
                                   atol=BF16_ATOL)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                   rtol=BF16_RTOL, atol=BF16_ATOL)
        np.testing.assert_allclose(np.asarray(z1), np.asarray(z0),
                                   rtol=BF16_RTOL, atol=BF16_ATOL)
        s, z = s0, z0
    live = jnp.asarray([True, False, True])
    args = (q[:, 0], k[:, 1], v[:, 2], logg[:, 3])
    decode = jax.jit(R.power_retention_decode)
    y, s1, z1 = decode(*args, s, z, rows, live)
    with jax.default_matmul_precision("highest"):
        want, s0, z0 = jax.jit(R.retention_decode_reference)(
            *(a.astype(f32) for a in args), s, z, rows, live)
    np.testing.assert_allclose(np.asarray(y, np.float32)[[0, 2]],
                               np.asarray(want)[[0, 2]], rtol=BF16_RTOL,
                               atol=BF16_ATOL)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5,
                               atol=1e-5)
    dead = int(rows[1])
    assert (np.asarray(s1)[dead] == np.asarray(s)[dead]).all()
    import time

    @jax.jit
    def many(q0, s, z):      # the state carried, so updated in place
        def one(c, _):
            return R.power_retention_decode(c[0], *args[1:], c[1], c[2],
                                            rows, live), None
        return jax.lax.scan(one, (q0, s, z), None, length=25)[0]

    jax.block_until_ready(many(args[0], s, z))
    t0 = time.perf_counter()
    jax.block_until_ready(many(args[0], s, z))
    ms = 1e3 * (time.perf_counter() - t0) / 25
    with capsys.disabled():
        print(f"\npower_retention_decode, 2 live rows of 3: {ms:.4f} ms a "
              f"call = {1e3 * ms / 2:.1f} us a live row (floor 83.2)")


def test_ssd_kernels_at_the_think_cell_widths(capsys):
    """Mamba-2's two kernels at Nemotron 3 Nano's widths (64 heads of 64, 8
    groups, state 128, bf16 activations, the float32 state of 33 slots): a
    chunk of 128 from the zero state and a second on the state it left, then
    a decode step over a work list with a dead row, each against its jnp
    form in float32, the dead row's state untouched; and the decode
    kernel's time a call at 19 live rows of 32 beside its bytes' floor
    (2 x 2.10 MB a row at 819 GB/s = 5.1 us a row)."""
    import functools
    import time

    from paddle_tpu.ops.pallas import ssd as S

    r = np.random.RandomState(5)
    H, P, G, N, L, SLOTS = 64, 64, 8, 128, 128, 33
    bf, f32 = jnp.bfloat16, jnp.float32

    def operands(b, n):
        x = jnp.asarray(r.randn(b, n, H, P).astype(np.float32), bf)
        bm = jnp.asarray(r.randn(b, n, G, N).astype(np.float32), bf)
        cm = jnp.asarray(r.randn(b, n, G, N).astype(np.float32), bf)
        dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(0.1),
                                          (b, n, H))).astype(np.float32))
        a = jnp.asarray(r.uniform(1.0, 16.0, (H,)).astype(np.float32))
        return x, dt, -dt * a, bm, cm

    rows = jnp.asarray(r.permutation(SLOTS)[:3], jnp.int32)
    state = jnp.zeros((SLOTS, H, P, N), f32)
    x, dt, da, bm, cm = operands(3, 2 * L)
    dt = S.mask_steps(dt, jnp.asarray([2 * L, L + 37, 2 * L]))
    da = jnp.where(dt > 0, da, 0.0)
    for sl in (slice(0, L), slice(L, 2 * L)):
        args = (x[:, sl], dt[:, sl], da[:, sl], bm[:, sl], cm[:, sl])
        y, s1 = jax.jit(S.ssd_chunk)(*args, state, rows)
        with jax.default_matmul_precision("highest"):
            want, s0 = jax.jit(S.ssd_chunk_reference)(
                *(a.astype(f32) for a in args), state, rows)
        scale = float(np.abs(np.asarray(want)).max())
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=BF16_RTOL, atol=BF16_ATOL * scale)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                   rtol=BF16_RTOL, atol=BF16_ATOL)
        state = s0
    live = jnp.asarray([True, False, True])
    args = (x[:, 0], dt[:, 1], jnp.exp(da[:, 1]), bm[:, 2], cm[:, 3])
    y, s1 = jax.jit(S.ssd_decode)(*args, state, rows, live)
    with jax.default_matmul_precision("highest"):
        want, s0 = jax.jit(S.ssd_decode_reference)(
            *(a.astype(f32) for a in args), state, rows, live)
    # the state's update is exact products in float32; y is one float32
    # product at ``highest``
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-5,
                               atol=1e-5)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(y)[[0, 2]],
                               np.asarray(want)[[0, 2]], rtol=1e-3,
                               atol=1e-3 * scale)
    dead = int(rows[1])
    assert (np.asarray(s1)[dead] == np.asarray(state)[dead]).all()

    xb, dtb, dab, bmb, cmb = operands(32, 1)
    live32 = jnp.asarray(np.arange(32) < 19)
    rows32 = jnp.arange(32, dtype=jnp.int32)
    forms = {f"heads_block {hb}": functools.partial(S.ssd_decode,
                                                    heads_block=hb)
             for hb in (16, 32, 64)}
    want32, _ = jax.jit(S.ssd_decode_reference)(
        xb[:, 0].astype(f32), dtb[:, 0], jnp.exp(dab[:, 0]),
        bmb[:, 0].astype(f32), cmb[:, 0].astype(f32), state, rows32, live32)
    for hb, form in forms.items():
        got32, _ = jax.jit(form)(xb[:, 0], dtb[:, 0], jnp.exp(dab[:, 0]),
                                 bmb[:, 0], cmb[:, 0], state, rows32, live32)
        np.testing.assert_allclose(np.asarray(got32), np.asarray(want32),
                                   rtol=1e-3, atol=1e-3 * float(
                                       np.abs(np.asarray(want32)).max()))

        @jax.jit
        def many(x0, s):     # the state carried, so updated in place
            def one(c, _):
                y, s = form(c[0], dtb[:, 0], jnp.exp(dab[:, 0]),
                            bmb[:, 0], cmb[:, 0], c[1], rows32, live32)
                return (c[0] + y.astype(bf) * 0, s), None
            return jax.lax.scan(one, (x0, s), None, length=25)[0]

        jax.block_until_ready(many(xb[:, 0], state))
        t0 = time.perf_counter()
        jax.block_until_ready(many(xb[:, 0], state))
        ms = 1e3 * (time.perf_counter() - t0) / 25
        with capsys.disabled():
            print(f"\nssd_decode {hb}, 19 live rows of 32: "
                  f"{ms:.4f} ms a call = {1e3 * ms / 19:.2f} us a live row "
                  f"(floor 5.12)")
