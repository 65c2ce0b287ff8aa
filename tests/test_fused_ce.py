"""Blockwise fused lm-head + cross-entropy (VERDICT r4 item 2).

Parity against the unfused materialize-the-logits path at f32, both weight
layouts, vocab padding, ignore_index, eager autograd through the registry,
and the LLaMA labels= training fast path (eager AND TrainStep-compiled).
Reference anchors: mp_ops.py:414 `_c_softmax_with_cross_entropy`,
c_softmax_with_cross_entropy_op.cu.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy as flce


def _dense(x, w, lab, transpose_y=True, ignore_index=-100):
    logits = (x @ (w.T if transpose_y else w)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    safe = jnp.where(lab == ignore_index, 0, lab)
    loss = -jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
    return jnp.where(lab == ignore_index, 0.0, loss)


@pytest.mark.parametrize("v,block", [(1000, 256), (1000, 0), (128, 0),
                                     (4096, 1024)])
def test_forward_parity(v, block):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((23, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((v, 32)) * 0.1, jnp.float32)
    lab = jnp.asarray(rng.integers(0, v, (23,)), jnp.int32).at[5].set(-100)
    got = flce(x, w, lab, block_size=block)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_dense(x, w, lab)),
                               rtol=1e-5, atol=1e-5)


def _labels(rng, n, v, case):
    lab = rng.integers(0, v, (n,))
    if case == "divides":
        return lab
    if case == "padded":  # labels in the padded last block's real columns
        lab[:4] = [v - 1, v - 2, 256, 257]
        lab[5] = -100
        return lab
    if case == "block_edges":  # first and last column of every block
        edges = [c for s in range(0, v, 128) for c in (s, min(s + 127,
                                                              v - 1))]
        lab[:len(edges)] = edges
        return lab
    if case == "ignored":
        lab[::3] = -100
        return lab
    return np.full((n,), -100)  # all_ignored


# (vocab, block, labels, upstream cotangent): 512 / 128 divides; 300 / 128
# pads its last block to 384 columns
_GRAD_CASES = {
    "divides": (512, 128, "divides", "mean"),
    "padded": (300, 128, "padded", "mean"),
    "block_edges": (300, 128, "block_edges", "mean"),
    "ignored": (300, 128, "ignored", "mean"),
    "all_ignored": (300, 128, "all_ignored", "mean"),
    "weighted": (300, 128, "padded", "weighted"),
}


@pytest.mark.parametrize("case", sorted(_GRAD_CASES))
@pytest.mark.parametrize("transpose_y", [True, False])
def test_grad_parity(transpose_y, case):
    """dx and dW against the dense f32 gradient: each vocabulary block
    folds its own one-hot columns into softmax - onehot."""
    v, block, labels, cot = _GRAD_CASES[case]
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((17, 24)), jnp.float32)
    w0 = jnp.asarray(rng.standard_normal((v, 24)) * 0.2, jnp.float32)
    w = w0 if transpose_y else w0.T
    lab = jnp.asarray(_labels(rng, 17, v, labels), jnp.int32)
    g = (jnp.full((17,), 1.0 / 17, jnp.float32) if cot == "mean" else
         jnp.asarray(rng.standard_normal(17), jnp.float32))

    gf = jax.grad(lambda x, w: (flce(x, w, lab, transpose_y=transpose_y,
                                     block_size=block) * g).sum(),
                  argnums=(0, 1))(x, w)
    gr = jax.grad(lambda x, w0: (_dense(x, w0, lab) * g).sum(),
                  argnums=(0, 1))(x, w0)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gr[0]),
                               rtol=1e-4, atol=1e-5)
    dw = gf[1] if transpose_y else gf[1].T
    np.testing.assert_allclose(np.asarray(dw), np.asarray(gr[1]),
                               rtol=1e-4, atol=1e-5)


def _hlo_ops(text):
    """(dtype, dims, opcode) of every instruction of an HLO module's text."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d), op)
            for dt, dims, op in re.findall(
                r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", text)]


@pytest.mark.parametrize("transpose_y", [True, False])
def test_grad_program_has_no_label_gather_or_f32_stack(transpose_y):
    """The gradient program of a padded bf16 head holds no gather, no
    scatter and no float32 (nblk, block, H) stack: each block's dW is
    final inside the block and written once in the weight's dtype. (The
    CPU itself widens some bf16 weight-sized arrays to f32 around its
    dots; tests/test_tpu_aot_compile.py asks the chip's compiler.)"""
    n, h, v, block = 64, 32, 1000, 256
    nblk = -(-v // block)
    x = jnp.zeros((n, h), jnp.bfloat16)
    w = jnp.zeros((v, h) if transpose_y else (h, v), jnp.bfloat16)
    lab = jnp.zeros((n,), jnp.int32)
    grad = jax.jit(jax.grad(
        lambda x, w: flce(x, w, lab, transpose_y=transpose_y,
                          block_size=block).sum(), argnums=(0, 1)))
    ops = _hlo_ops(grad.lower(x, w).compile().as_text())
    assert not [op for _, _, op in ops if op in ("gather", "scatter")]
    stacks = [(dt, dims) for dt, dims, _ in ops
              if dt == "f32" and len(dims) == 3
              and int(np.prod(dims)) == nblk * block * h]
    assert not stacks, stacks
    gx, gw = grad(x, w)
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16


def test_bf16_accumulates_f32():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((16, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((256, 32)) * 0.1, jnp.bfloat16)
    lab = jnp.asarray(rng.integers(0, 256, (16,)), jnp.int32)
    got = flce(x, w, lab)
    assert got.dtype == jnp.float32
    want = _dense(x.astype(jnp.float32), w.astype(jnp.float32), lab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # grads come back in the operand dtypes
    gx, gw = jax.grad(lambda x, w: flce(x, w, lab).sum(),
                      argnums=(0, 1))(x, w)
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16


def test_public_op_eager_autograd():
    rng = np.random.default_rng(3)
    x = paddle.to_tensor(rng.standard_normal((2, 9, 16)).astype(np.float32))
    x.stop_gradient = False
    w = paddle.to_tensor((rng.standard_normal((200, 16)) * 0.1)
                         .astype(np.float32))
    w.stop_gradient = False
    lab = paddle.to_tensor(rng.integers(0, 200, (2, 9)).astype(np.int64))
    loss = paddle.ops.fused_linear_cross_entropy(x, w, lab)
    assert loss.shape == [2, 9]
    loss.mean().backward()

    xa, wa = jnp.asarray(x._value), jnp.asarray(w._value)
    la = jnp.asarray(lab._value)
    gr = jax.grad(
        lambda x, w: _dense(x.reshape(-1, 16), w,
                            la.reshape(-1).astype(jnp.int32)).mean(),
        argnums=(0, 1))(xa, wa)
    np.testing.assert_allclose(np.asarray(x.grad._value),
                               np.asarray(gr[0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w.grad._value),
                               np.asarray(gr[1]), rtol=1e-4, atol=1e-5)


def _tiny_cfg(tie):
    from paddle_tpu.models import LlamaConfig

    return LlamaConfig(vocab_size=211, hidden_size=32, intermediate_size=64,
                       num_hidden_layers=2, num_attention_heads=4,
                       max_position_embeddings=64, tie_word_embeddings=tie)


@pytest.mark.parametrize("tie", [True, False])
def test_llama_labels_path_matches_criterion(tie):
    """model(ids, labels=ids) (fused, no logits buffer) must equal
    criterion(model(ids), ids) (unfused) — loss AND parameter grads."""
    from paddle_tpu.models import LlamaForCausalLM, LlamaPretrainingCriterion

    paddle.seed(0)
    model = LlamaForCausalLM(_tiny_cfg(tie))
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 211, (2, 17)).astype(np.int32))
    # right-padded labels: ignore_index=-100 rows must be masked by BOTH
    # paths (the dense op clamps+masks, the fused op zeroes the pick)
    lab_np = np.asarray(ids._value).copy()
    lab_np[:, -3:] = -100
    labels = paddle.to_tensor(lab_np)

    loss_f = model(ids, labels=labels)
    loss_f.backward()
    g_fused = {k: np.asarray(p.grad._value).copy()
               for k, p in model.named_parameters() if p.grad is not None}
    model.clear_gradients()

    crit = LlamaPretrainingCriterion()
    loss_u = crit(model(ids), labels)
    np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-5)

    # (B, S, 1) trailing-singleton label layout must also work fused
    loss_3d = model(ids, labels=paddle.to_tensor(lab_np[..., None]))
    np.testing.assert_allclose(float(loss_3d), float(loss_u), rtol=1e-5)
    loss_u.backward()
    for k, p in model.named_parameters():
        if p.grad is None:
            continue
        np.testing.assert_allclose(
            g_fused[k], np.asarray(p.grad._value), rtol=2e-4, atol=1e-5,
            err_msg=f"grad mismatch for {k}")


def test_llama_labels_path_tp_fallback():
    """Vocab-sharded (TP) lm-head must NOT take the blockwise kernel (its
    dynamic-slice walk would all-gather the weight every block); the
    labels= path reroutes to sharded logits + c_softmax_with_cross_entropy
    and matches the replicated fused loss."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import _vocab_dim_sharded, llama_shard_fn

    cfg = LlamaConfig(vocab_size=256, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=64, tie_word_embeddings=True)
    ids = paddle.to_tensor(
        np.random.RandomState(5).randint(0, 256, (4, 12)).astype(np.int32))
    try:
        mesh = dist.ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])
        dist.set_mesh(mesh)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        dist.shard_layer(model, mesh, llama_shard_fn(mesh))
        w = model.model.embed_tokens.weight
        assert _vocab_dim_sharded(w, 0), "shard plan must mark vocab sharded"
        loss_tp = model(ids, labels=ids)
    finally:
        dist.process_mesh._global_mesh = None

    paddle.seed(0)
    rep = LlamaForCausalLM(cfg)
    assert not _vocab_dim_sharded(rep.model.embed_tokens.weight, 0)
    loss_rep = rep(ids, labels=ids)
    np.testing.assert_allclose(float(loss_tp), float(loss_rep),
                               rtol=1e-4, atol=1e-5)


def test_gpt_labels_path_matches_criterion():
    """GPT shares the causal_lm_loss labels= path: fused loss == unfused
    criterion loss (tied embeddings)."""
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    cfg = GPTConfig(vocab_size=197, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64,
                    hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    ids = paddle.to_tensor(
        np.random.RandomState(2).randint(0, 197, (2, 13)).astype(np.int32))
    loss_f = m(ids, labels=ids)
    loss_u = GPTPretrainingCriterion()(m(ids), ids)
    np.testing.assert_allclose(float(loss_f), float(loss_u), rtol=1e-5)


def test_llama_labels_path_compiled_trainstep():
    """Fused loss through TrainStep.run: losses must track the unfused
    TrainStep step-for-step."""
    from paddle_tpu.models import LlamaForCausalLM, LlamaPretrainingCriterion

    ids_np = np.random.RandomState(1).randint(0, 211, (2, 17)).astype(np.int32)

    paddle.seed(0)
    m1 = LlamaForCausalLM(_tiny_cfg(True))
    o1 = paddle.optimizer.AdamW(learning_rate=1e-3,
                                parameters=m1.parameters())
    ids = paddle.to_tensor(ids_np)
    # model called with labels positionally (attn_mask=None, caches=None)
    s1 = paddle.jit.TrainStep(m1, lambda loss: loss, o1)
    l1 = np.asarray(s1.run(ids, None, None, ids, steps=3)._value)

    paddle.seed(0)
    m2 = LlamaForCausalLM(_tiny_cfg(True))
    o2 = paddle.optimizer.AdamW(learning_rate=1e-3,
                                parameters=m2.parameters())
    crit = LlamaPretrainingCriterion()
    s2 = paddle.jit.TrainStep(m2, lambda logits, lab: crit(logits, lab), o2)
    l2 = np.asarray(s2.run(ids, labels=ids, steps=3)._value)
    np.testing.assert_allclose(l1, l2, rtol=1e-4, atol=1e-5)


# (leading shape of the rows, which labels are ignored). The mean path's
# chunk is H held to [1024, 2048] and never more than the rows rounded up
# to 8; rows (B, T) walk T in chunks of (B, T_c), rows of one axis or more
# than a chunk of sequences walk the flattened rows. So 2,500 rows walk
# three chunks of 840 (the last padded), 2,048 rows two whole ones of
# 1,024, 37 or 45 rows one chunk of 40 or 48, wider than the rows; 3 x 900
# rows walk three chunks of 3 x 304 (each sequence padded by 12 rows), and
# 1,100 x 2 rows, more sequences than a chunk's 1,024 rows, walk three
# flattened chunks of 736
_MEAN_CASES = {
    "ragged_chunks": ((2500,), "some"),
    "whole_chunks": ((2048,), "every_third"),
    "chunk_wider_than_rows": ((37,), "some"),
    "all_ignored": ((45,), "all"),
    "sequences_ragged": ((3, 900), "every_third"),
    "more_sequences_than_a_chunk": ((1100, 2), "some"),
}


def _rel_gaps(got, want):
    """(norm-relative, max-relative) gap of got against want; the absolute
    gap where want is all zeros."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = got - want
    norm, peak = np.linalg.norm(want), np.abs(want).max()
    return (float(np.linalg.norm(err) / (norm or 1.0)),
            float(np.abs(err).max() / (peak or 1.0)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_MEAN_CASES))
@pytest.mark.parametrize("transpose_y", [True, False])
def test_mean_path_matches_per_token_mean_and_dense(transpose_y, case,
                                                    dtype):
    """reduction="mean" (the row-chunk walk that forms the gradient in its
    forward) gives the loss, dx and dW of the per-token path's .mean() and
    of a float32 dense reference; the division is by ALL rows, ignored
    rows included."""
    lead, ignored = _MEAN_CASES[case]
    n = int(np.prod(lead))
    h, v = 16, 300
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((n, h)), dt)
    w0 = jnp.asarray(rng.standard_normal((v, h)) * 0.3, dt)
    w = w0 if transpose_y else w0.T
    lab = rng.integers(0, v, (n,))
    if ignored == "some":
        lab[[1, 5, n - 1]] = -100
    elif ignored == "every_third":
        lab[::3] = -100
    else:
        lab[:] = -100
    x = x.reshape(lead + (h,))
    lab = jnp.asarray(lab, jnp.int32).reshape(lead)

    def mean(x, w):
        return flce(x, w, lab, transpose_y=transpose_y, reduction="mean")

    lm, gm = jax.value_and_grad(mean, argnums=(0, 1))(x, w)
    lt, gt = jax.value_and_grad(
        lambda x, w: flce(x, w, lab, transpose_y=transpose_y).mean(),
        argnums=(0, 1))(x, w)
    lr, gr = jax.value_and_grad(
        lambda x, w0: _dense(x, w0, lab).mean(), argnums=(0, 1))(
            x.astype(jnp.float32), w0.astype(jnp.float32))
    assert lm.shape == () and lm.dtype == jnp.float32
    assert gm[0].dtype == gm[1].dtype == dt
    np.testing.assert_allclose(float(mean(x, w)), float(lm), rtol=1e-6)
    np.testing.assert_allclose(float(lm), float(lt), rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(float(lm), float(lr), rtol=1e-5, atol=1e-7)
    # f32: the same sums in another order; bf16: dl rounded to bf16 once as
    # the products' operand, and dx, dW once on the way out
    limit = 1e-5 if dt == jnp.float32 else 1e-2
    dw_m = gm[1] if transpose_y else gm[1].T
    dw_t = gt[1] if transpose_y else gt[1].T
    for name, got, want in (("dx/per-token", gm[0], gt[0]),
                            ("dW/per-token", dw_m, dw_t),
                            ("dx/dense", gm[0], gr[0]),
                            ("dW/dense", dw_m, gr[1])):
        norm, peak = _rel_gaps(got, want)
        assert norm < limit / 2 and peak < limit, (name, norm, peak)


def _walks():
    from paddle_tpu.core import telemetry

    c = telemetry.counter("ops.fused_ce_walk_total")
    return c.value(walk="token"), c.value(walk="vocab")


@pytest.mark.parametrize("transpose_y", [True, False])
def test_mean_gradient_program_has_three_vocabulary_products(transpose_y):
    """The mean path's gradient program holds three products, each with the
    vocabulary as a dimension (logits, dx, dW), where the per-token path's
    holds four; no gather, no scatter and no dynamic-update-slice of a
    vocabulary-wide array (dW is one accumulator, not blocks written at a
    column offset). Tracing it counts one token walk; the per-token path
    counts a vocabulary walk."""
    n, h, v = 2100, 32, 1000
    x = jnp.zeros((n, h), jnp.bfloat16)
    w = jnp.zeros((v, h) if transpose_y else (h, v), jnp.bfloat16)
    lab = jnp.zeros((n,), jnp.int32)

    def program(reduction):
        def loss(x, w):
            out = flce(x, w, lab, transpose_y=transpose_y,
                       reduction=reduction)
            return out if reduction == "mean" else out.mean()
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w)

    before = _walks()
    lowered = program("mean")
    assert _walks() == (before[0] + 1, before[1])
    dots = re.findall(r"stablehlo\.dot_general .*?: \((.*?)\) ->",
                      lowered.as_text())
    assert len(dots) == 3, dots
    assert all(f"x{v}x" in d or f"<{v}x" in d for d in dots), dots
    ops = _hlo_ops(lowered.compile().as_text())
    assert sum(op == "dot" for _, _, op in ops) == 3
    assert not [op for _, _, op in ops if op in ("gather", "scatter")]
    assert not [dims for _, dims, op in ops
                if op == "dynamic-update-slice" and v in dims]

    before = _walks()
    per_token = program("none")
    assert _walks() == (before[0], before[1] + 1)
    assert len(re.findall(r"stablehlo\.dot_general", per_token.as_text())) \
        == 4


def test_mean_chunk_is_held_by_the_logits_budget():
    """The mean path's chunk: H held to [1024, 2048], never more than the
    rows rounded up to 8, and no more rows than put 768 MiB in a chunk's
    float32 logits, a multiple of 128."""
    from paddle_tpu.ops.fused_ce import _pick_chunk

    assert _pick_chunk(8190, 2048, 92544) == 2048   # the training cell
    assert _pick_chunk(8190, 4096, 92544) == 2048
    assert _pick_chunk(8190, 512, 92544) == 1024
    assert _pick_chunk(37, 2048, 92544) == 40
    assert _pick_chunk(8190, 2048, 256000) == 768
    assert _pick_chunk(8190, 2048, 4 << 20) == 128
    for v in (32000, 92544, 152064, 256000):
        assert _pick_chunk(1 << 20, 2048, v) * v * 4 <= 768 << 20


def test_reduction_must_be_none_or_mean():
    x = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="reduction"):
        flce(x, jnp.zeros((16, 8)), jnp.zeros((4,), jnp.int32),
             reduction="sum")
