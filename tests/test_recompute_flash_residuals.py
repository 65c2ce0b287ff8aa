"""What the traced ``recompute`` keeps: the five arrays the flash backward reads.

``fleet.recompute`` under a trace is ``jax.checkpoint`` with a policy that
saves the residuals ``_flash_fwd_rule`` names: q, k and v as the kernel
takes them, ``out`` and ``lse`` as it writes them. The backward of a
checkpointed block then does NOT run ``flash_fwd`` again (PR 32) and does
NOT rebuild q, k and v: no second q / k / v product, rope or swap to
``(b, h, s, d)`` (PR 34). These cases count the kernels and the products in
the gradient program (a property of the traced program: nothing runs for the
counts), hold the results to those of the unrecomputed model, and hold
everything that has no such names to what it lowered to before.
"""
import collections
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed.fleet import recompute
from paddle_tpu.jit import _FunctionalModel
from paddle_tpu.models import llama
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import kernel_mesh

from _jaxpr import pallas_names, walk

LAYERS, BATCH, SEQ = 3, 2, 128
CASES = pytest.mark.parametrize("kv_heads,packed", [
    (4, False), (2, False), (4, True), (2, True),
], ids=["mha-causal", "gqa-causal", "mha-segments", "gqa-segments"])


def _pack(monkeypatch):
    """``LlamaAttention`` has no argument for packed documents; where a case
    asks for them, its attention call gets ``segment_ids`` here, which is the
    masked variant of all three kernels."""
    ids = np.repeat(np.arange(4, dtype=np.int32), SEQ // 4)[None].repeat(
        BATCH, 0)
    monkeypatch.setattr(
        llama, "scaled_dot_product_attention",
        functools.partial(llama.scaled_dot_product_attention,
                          segment_ids=ids))


def _loss_fn(use_recompute, kv_heads):
    """``(loss(params, ids), params, ids)`` of a tiny ``LlamaForCausalLM``
    (fused lm-head + CE, as the training cell runs it); same seed, so the
    two settings of ``use_recompute`` hold the same weights."""
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=LAYERS, num_key_value_heads=kv_heads,
        max_position_embeddings=SEQ, use_recompute=use_recompute))
    functional = _FunctionalModel(model)
    params = {k: p._value for k, p in model.named_parameters()}
    buffers = {k: b._value for k, b in model.named_buffers()}
    key = jax.random.key_data(jax.random.key(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, model.config.vocab_size, (BATCH, SEQ)), jnp.int32)

    def loss(params, ids):
        return functional(params, buffers, (ids,), {"labels": ids}, key)[0]

    return loss, params, ids


def _kernels(fn, *operands):
    return collections.Counter(pallas_names(
        jax.make_jaxpr(fn)(*operands).jaxpr))


@CASES
def test_recomputed_gradient_program_runs_flash_fwd_once_a_layer(
        monkeypatch, kv_heads, packed):
    """Twice a layer before the policy: once in the forward, once more in
    every layer's recomputation."""
    if packed:
        _pack(monkeypatch)
    loss, params, ids = _loss_fn(True, kv_heads)
    kernels = _kernels(jax.value_and_grad(loss), params, ids)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"):
        assert kernels[name] == LAYERS, (name, kernels)


def _program_counts(fn, *operands):
    """Primitive names (outside any kernel's body) and kernel names of the
    traced ``fn``, each with how often the program holds it."""
    jaxpr = jax.make_jaxpr(fn)(*operands).jaxpr
    primitives = collections.Counter(
        eqn.primitive.name for eqn, enclosing in walk(jaxpr)
        if "pallas_call" not in enclosing)
    return primitives, collections.Counter(pallas_names(jaxpr))


@CASES
def test_recomputed_gradient_program_rebuilds_neither_q_nor_k_nor_v(
        monkeypatch, kv_heads, packed):
    """A recomputed layer runs six products more than an unrecomputed one
    without the three names (q, k, v, o, gate, up) and three with them (o,
    gate, up); its ropes are the forward's and the backward's alone, as if
    nothing were recomputed; and the swaps of q, k and v to ``(b, h, s, d)``
    go with them (7 of the 8 transposes a recomputed layer added: what is
    left is ``out``'s swap back for the o product)."""
    if packed:
        _pack(monkeypatch)
    (plain, plain_kernels), (kept, kept_kernels) = (
        _program_counts(jax.value_and_grad(loss), params, ids)
        for loss, params, ids in (_loss_fn(rc, kv_heads)
                                  for rc in (False, True)))
    assert kept["dot_general"] == plain["dot_general"] + 3 * LAYERS
    assert kept_kernels["fused_rope"] == plain_kernels["fused_rope"] \
        == 4 * LAYERS
    assert kept["transpose"] == plain["transpose"] + LAYERS


@CASES
def test_gradient_program_names_the_five_residuals_once_a_layer(
        monkeypatch, kv_heads, packed):
    """In the rule's order, inside every layer's checkpoint; the policy's
    saved set is these five and nothing else."""
    if packed:
        _pack(monkeypatch)
    loss, params, ids = _loss_fn(True, kv_heads)
    named = [eqn.params["name"] for eqn, _ in walk(
        jax.make_jaxpr(jax.value_and_grad(loss))(params, ids).jaxpr)
        if eqn.primitive.name == "name"]
    assert named == list(fa.FLASH_RESIDUAL_NAMES) * LAYERS


@CASES
def test_recompute_changes_neither_the_loss_nor_any_gradient(
        monkeypatch, kv_heads, packed):
    """To the bit on the CPU: the kept arrays are what the recomputation
    produced, from the same operations on the same operands, and everything
    else is recomputed as before."""
    if packed:
        _pack(monkeypatch)
    (l0, g0), (l1, g1) = (
        jax.jit(jax.value_and_grad(loss))(params, ids)
        for loss, params, ids in (_loss_fn(rc, kv_heads)
                                  for rc in (False, True)))
    assert float(l0) == float(l1)
    assert set(g0) == set(g1)
    for k in g0:
        np.testing.assert_array_equal(np.asarray(g0[k]), np.asarray(g1[k]),
                                      err_msg=k)


def test_recomputed_flash_fwd_count_holds_under_a_kernel_mesh():
    """``flash_attention`` under ``kernel_mesh`` runs its custom-vjp inside
    ``shard_map``: the names are given there, and the policy outside still
    keeps them (four virtual devices, heads split four ways)."""
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("mp",))
    loss, params, ids = _loss_fn(True, 4)

    def scoped(params, ids):
        with kernel_mesh(mesh, head_axis="mp"):
            return loss(params, ids)

    kernels = _kernels(jax.value_and_grad(scoped), params, ids)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"):
        assert kernels[name] == LAYERS, (name, kernels)
    want = jax.jit(jax.value_and_grad(loss))(params, ids)
    got = jax.jit(jax.value_and_grad(scoped))(params, ids)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for k in want[1]:
        np.testing.assert_allclose(np.asarray(got[1][k]),
                                   np.asarray(want[1][k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


def _tpu_text(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()


def test_a_block_without_flash_attention_lowers_as_a_bare_checkpoint():
    """No names in the block, nothing kept: ``recompute`` of it is
    ``jax.checkpoint`` of it, to the text."""
    w = jnp.asarray(np.random.RandomState(0).randn(16, 16), jnp.float32)

    def block(x):
        return jnp.tanh(x @ w) @ w.T

    def through_recompute(x):
        y = recompute(lambda t: Tensor._from_value(block(t._value)),
                      Tensor._from_value(x))
        return (y._value ** 2).sum()

    def bare(x):
        def pure(vals):
            return block(vals[0])

        return (jax.checkpoint(pure)([x]) ** 2).sum()

    x = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    ours = jax.jit(jax.grad(through_recompute)).trace(x).lower().as_text()
    plain = jax.jit(jax.grad(bare)).trace(x).lower().as_text()
    assert ours.replace("through_recompute", "bare") == plain


@pytest.mark.parametrize("differentiated", [False, True],
                         ids=["forward", "gradient"])
def test_outside_a_policy_the_names_lower_to_nothing(monkeypatch,
                                                     differentiated):
    """Lowered for the TPU (nothing runs). The forward alone, which is what
    every serving cell's ``jit_prefill`` traces, never reaches the rule: no
    ``name`` equation. A gradient with no checkpoint around it holds the five
    names, in the rule's order, and lowers to the text it lowers to with the
    names taken out."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, S, H, KVH, D = 2, 256, 4, 2, 128
    avals = [jax.ShapeDtypeStruct((B, S, n, D), jnp.bfloat16)
             for n in (H, KVH, KVH)]

    def forward(q, k, v):
        return fa.flash_attention(q, k, v, is_causal=True)

    def loss(q, k, v):
        return forward(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if differentiated else forward
    named = [e.params["name"] for e in jax.make_jaxpr(fn)(*avals).jaxpr.eqns
             if e.primitive.name == "name"]
    assert named == ([fa.FLASH_Q_NAME, fa.FLASH_K_NAME, fa.FLASH_V_NAME,
                      fa.FLASH_OUT_NAME, fa.FLASH_LSE_NAME]
                     if differentiated else [])
    text = _tpu_text(fn, *avals)
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert _tpu_text(fn, *avals) == text
