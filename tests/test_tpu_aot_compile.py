"""Ask the chip's compiler, without the chip.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached (``v5e:2x2``). These cases hand it the
main path's Pallas kernels at the widths ``chip_smoke.py`` runs — what
interpret mode cannot show: block shapes Mosaic refuses, vector layouts it
cannot infer, kernels GSPMD cannot partition. Nothing runs, so nothing here
says anything about results or times; a compile that passes is not a chip
run. Code that asks ``jax.default_backend()`` would take its CPU branch, so
the fixture steers that HERE (no option of the program does). JAX's
persistent compile cache is off around the module: it can write such an
executable but not read it back without a chip.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas.decode_attention import paged_attention
from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                   flash_attention_paged)
from paddle_tpu.ops.pallas.fused_ops import fused_rope
from paddle_tpu.ops.pallas.rms_norm import rms_norm

BF16 = jnp.bfloat16
MOSAIC_CALL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def _as_if_on_the_chip(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, topo, *shapes):
    one_chip = SingleDeviceSharding(topo.devices[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    compiled = jax.jit(fn).lower(*avals).compile()
    assert MOSAIC_CALL in compiled.as_text()
    return compiled


@pytest.mark.parametrize("b,heads,kv_heads,pages", [
    (8, 32, 32, 512),
    (8, 32, 8, 512),
    # the mistral7b-chat-open cell: 32 slots, 512 pool pages + 1 scratch
    (32, 32, 8, 513),
], ids=["mha32x128", "gqa32_8x128", "chat_cell_b32"])
def test_paged_attention_compiles(topo, b, heads, kv_heads, pages):
    page, per_seq, d = 128, 32, 128
    _compile(paged_attention, topo,
             ((b, heads, d), BF16), ((pages, page, kv_heads, d), BF16),
             ((pages, page, kv_heads, d), BF16), ((b, per_seq), jnp.int32),
             ((b,), jnp.int32))


def test_paged_mla_attention_compiles_at_the_reason_cell_shapes(topo):
    """joyai-flash-reason-open: 32 slots, 32 heads against one latent of
    512 + 64, 1,024 pool pages + 1 scratch, 32 table columns."""
    from paddle_tpu.ops.pallas.mla_attention import paged_mla_attention

    _compile(lambda *a: paged_mla_attention(*a, 192 ** -0.5,
                                            pages_per_seq=32), topo,
             ((32, 32, 512), BF16), ((32, 32, 64), BF16),
             ((1025, 128, 512), BF16), ((1025, 128, 64), BF16),
             ((32, 33), jnp.int32), ((32,), jnp.int32))


@pytest.mark.parametrize("kernel", ["decode", "chunk_g4", "chunk_g32"])
def test_power_retention_kernels_compile_at_the_continue_cell_shapes(
        topo, kernel):
    """brumby14b-continue-open: 32 slots + the scratch slot, 40 query heads
    over 8 kv heads of 128, the float32 state in its 65 x 128 x 128 layout
    a head (a 4.26 MB block in and out: the raised VMEM limit), updated in
    place."""
    from paddle_tpu.ops.pallas import retention as R

    s_shape, z_shape = R.state_shapes(8, 128)
    state = (((33,) + s_shape, jnp.float32), ((33,) + z_shape, jnp.float32))
    if kernel == "decode":
        fn, shapes = R.power_retention_decode, (
            ((32, 40, 128), BF16), ((32, 8, 128), BF16),
            ((32, 8, 128), BF16), ((32, 8), jnp.float32), *state,
            ((32,), jnp.int32), ((32,), jnp.bool_))
    else:
        g = int(kernel.split("_g")[1])
        fn, shapes = R.power_retention_chunk, (
            ((g, 128, 40, 128), BF16), ((g, 128, 8, 128), BF16),
            ((g, 128, 8, 128), BF16), ((g, 128, 8), jnp.float32), *state,
            ((g,), jnp.int32))
    one_chip = SingleDeviceSharding(topo.devices[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    # donated as the engine donates it: the state is aliased through the
    # kernel, not copied (1.13 GB of it a layer)
    compiled = jax.jit(fn, donate_argnums=(4, 5)).lower(*avals).compile()
    assert MOSAIC_CALL in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes > 1.1e9
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2e9


@pytest.mark.parametrize("m,k,n", [
    (256, 2048, 1536), (256, 768, 2048),        # a decode step: 32 x top-8
    (32768, 2048, 1536), (32768, 768, 2048),    # a 32 x 128 prefill
], ids=["decode_gate_up", "decode_down", "prefill_gate_up", "prefill_down"])
def test_moe_gmm_compiles_at_the_published_expert_shapes(topo, m, k, n):
    from paddle_tpu.ops.pallas.moe_gmm import moe_gmm

    _compile(moe_gmm, topo, ((m, k), BF16), ((256, k, n), BF16),
             ((256,), jnp.int32))


@pytest.mark.parametrize("m,k,n", [
    (256, 2688, 1920), (256, 1856, 2688),       # a decode step: 32 x top-6
    (24576, 2688, 1920),                        # a 32 x 128 prefill
], ids=["decode_up", "decode_down", "prefill_up"])
def test_moe_gmm_compiles_at_the_think_cells_ungated_expert_shapes(topo, m, k,
                                                                   n):
    """nemotron3nano-think-open: 64 held experts of 1,856, the first stack
    stored 1,920 columns wide. Neither width is a multiple of 512, so the
    column tile is 384 (a whole (2688, 1856) matrix a block would need 20
    MB of VMEM; a stack 1,856 wide is laid out transposed by the chip's
    compiler and copied whole, 640 MB, on its way to the kernel). No copy of
    the stack is in what compiled."""
    from paddle_tpu.ops.pallas.moe_gmm import col_tile, moe_gmm

    assert col_tile(n) == 384 and col_tile(1536) == col_tile(2048) == 512
    compiled = _compile(moe_gmm, topo, ((m, k), BF16), ((64, k, n), BF16),
                        ((64,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * k * n


@pytest.mark.parametrize("kernel", ["decode", "chunk_g1", "chunk_g32"])
def test_ssd_kernels_compile_at_the_think_cell_shapes(topo, kernel):
    """nemotron3nano-think-open: 32 slots + the scratch slot, 64 Mamba-2
    heads of 64 in 8 groups, state 128, the float32 state (2.1 MB a slot a
    layer) updated in place."""
    from paddle_tpu.ops.pallas import ssd as S

    f32 = jnp.float32
    state = ((33, 64, 64, 128), f32)
    if kernel == "decode":
        fn, shapes, donate = S.ssd_decode, (
            ((32, 64, 64), BF16), ((32, 64), f32), ((32, 64), f32),
            ((32, 8, 128), BF16), ((32, 8, 128), BF16), state,
            ((32,), jnp.int32), ((32,), jnp.bool_)), 5
    else:
        g = int(kernel.split("_g")[1])
        fn, shapes, donate = S.ssd_chunk, (
            ((g, 128, 64, 64), BF16), ((g, 128, 64), f32),
            ((g, 128, 64), f32), ((g, 128, 8, 128), BF16),
            ((g, 128, 8, 128), BF16), state, ((g,), jnp.int32)), 5
    one_chip = SingleDeviceSharding(topo.devices[0])
    avals = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
             for s, d in shapes]
    compiled = jax.jit(fn, donate_argnums=(donate,)).lower(*avals).compile()
    assert MOSAIC_CALL in compiled.as_text()
    # the state is aliased through the kernel, not copied
    assert compiled.memory_analysis().alias_size_in_bytes >= 33 * 2_097_152


def test_a_two_kv_head_page_pool_is_not_padded_on_the_chip(topo):
    """A page of (128 tokens, 2 kv heads, 128) bfloat16: the chip's compiler
    lays the pool out in (2, 128) tiles, 1,024 bytes a token a layer and not
    the eightfold of a (16, 128) tile, so the hybrid's attention keeps
    ``paged_attention``'s (pages, page, kv heads, head size) layout."""
    pages = 1025
    compiled = _compile(
        paged_attention, topo, ((32, 32, 128), BF16),
        ((pages, 128, 2, 128), BF16), ((pages, 128, 2, 128), BF16),
        ((32, 32), jnp.int32), ((32,), jnp.int32))
    text = compiled.as_text()
    assert "bf16[1025,128,2,128]{3,2,1,0:T(2,128)(2,1)}" in text
    assert compiled.memory_analysis().argument_size_in_bytes < \
        2 * pages * 128 * 2 * 128 * 2 * 1.01 + 1e6


@pytest.mark.parametrize("seq,heads,kv_heads", [
    (2048, 32, 32),
    # the internlm2-d12-pretrain-1chip cell: the dk/dv kernel holds a query
    # head's whole-sequence q / do / lse / delta blocks (12 MiB of VMEM)
    (4096, 16, 8),
], ids=["mha32x128_s2048", "train_cell_gqa16_8_s4096"])
def test_flash_attention_fwd_bwd_compiles(topo, seq, heads, kv_heads):
    def loss(q, k, v):
        return flash_attention(q, k, v, is_causal=True).astype(
            jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), topo,
             *[((2, seq, n, 128), BF16) for n in (heads, kv_heads, kv_heads)])


def test_recomputed_gradient_program_compiles_at_the_train_cell_widths(topo):
    """Two layers of ``internlm2-d12-pretrain-1chip`` (hidden 2048, 16 / 8
    heads of 128, SwiGLU 8192, vocabulary 92,544, 2 x 4096 tokens, bf16,
    fused lm-head + CE) under ``use_recompute``: the chip's compiler takes
    the gradient program with the five arrays the flash backward reads kept
    across the checkpoint (q, k, v and ``out`` as ``(b, h, s, d)``, ``lse``
    as the kernel writes it, ``f32[b, h, s, 1]``), and what it compiled runs
    ``flash_fwd`` once a layer and ``fused_rope`` four times (forward and
    backward, q and k; six with q, k and v rebuilt in the recomputation).
    The loss is the per-token fused lm-head + CE's mean over the model's
    hidden states, so what is measured is the kept set and the layers; the
    training criterion's mean path has its own budget
    (``test_fused_lm_head_mean_at_the_train_cell_head``). The compiler's
    temporaries read 1,049,316,352 B here (0.98 GiB; 1.82 until PR 36, when
    the lm-head's float32 weight-gradient stack, its relayout and its
    scatter-add went): the bound leaves 3 % over that (31 MiB), less than
    two layers of the next candidates' own bytes (a kept gate 256 MiB, a
    kept ``h1`` 64), so a later name cannot grow the kept set unseen."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu.jit import _FunctionalModel
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    layers = 2
    paddle.set_default_dtype("bfloat16")
    try:
        with paddle.LazyGuard():
            model = LlamaForCausalLM(LlamaConfig(
                vocab_size=92544, hidden_size=2048, intermediate_size=8192,
                num_hidden_layers=layers, num_attention_heads=16,
                num_key_value_heads=8, max_position_embeddings=4096,
                rope_theta=1e6, use_recompute=True))
    finally:
        paddle.set_default_dtype("float32")
    functional = _FunctionalModel(model.model)
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = {k: jax.ShapeDtypeStruct(p._lazy_init[1], BF16,
                                      sharding=one_chip)
              for k, p in model.model.named_parameters()}
    head = jax.ShapeDtypeStruct(model.lm_head.weight._lazy_init[1], BF16,
                                sharding=one_chip)
    buffers = {k: b._value for k, b in model.model.named_buffers()}

    def loss(params, head, ids, key):
        hidden = functional(params, buffers, (ids,), {}, key)[0]
        return fused_linear_cross_entropy(
            hidden[:, :-1, :], head, ids[:, 1:], transpose_y=False).mean()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, head,
        jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    for kernel, a_layer in (("flash_fwd", 1), ("flash_bwd_dq", 1),
                            ("flash_bwd_dkdv", 1), ("fused_rope", 4)):
        calls = re.findall(rf"^\s*%?{kernel}(?:\.\d+)? = .*{MOSAIC_CALL}",
                           text, re.M)
        assert len(calls) == a_layer * layers, (kernel, len(calls))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.01 * 2 ** 30


@pytest.mark.parametrize("transpose_y", [False, True], ids=["HV", "VH"])
def test_fused_lm_head_gradient_at_the_train_cell_head(topo, transpose_y):
    """The fused lm-head + CE's gradient program at the training cell's
    head (2 x 4096 tokens, hidden 2048, vocabulary 92,544 in 23 blocks of
    4,096, the last padded; bf16, a weighted sum of the per-token loss):
    the chip's compiler keeps no gather and no scatter of the backward
    (the forward's label pick is one gather of a logit a token) and no
    float32 array of the vocabulary's size: each block's dW is written
    once, in bf16, into the weight's own layout."""
    import re

    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    n, h, v = 8192, 2048, 92544
    one_chip = SingleDeviceSharding(topo.devices[0])

    def loss(x, w, lab, g):
        return (fused_linear_cross_entropy(x, w, lab, transpose_y=transpose_y)
                * g).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((n, h), BF16), ((v, h) if transpose_y else (h, v), BF16),
            ((n,), jnp.int32), ((n,), jnp.float32))]).compile().as_text()
    ops = re.findall(r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(.*?"
                     r"op_name=\"([^\"]*)\"", text)
    backward = [(op, name) for _, _, op, name in ops
                if op in ("gather", "scatter") and "transpose(" in name]
    assert not backward, backward
    big = [(dt, dims, op) for dt, dims, op, _ in ops if dt == "f32"
           and int(np.prod([int(d) for d in dims.split(",") if d])) >= v * h]
    assert not big, big


def _hlo_ops(text):
    """(dtype, dims, opcode) of every instruction of an HLO module text."""
    import re

    return [(dt, tuple(int(d) for d in dims.split(",") if d), op)
            for dt, dims, op in re.findall(
                r"= (\w+)\[([\d,]*)\]\S* ([\w-]+)\(", text)]


@pytest.mark.parametrize("transpose_y", [False, True], ids=["HV", "VH"])
def test_fused_lm_head_mean_at_the_train_cell_head(topo, transpose_y):
    """The mean path (``reduction="mean"``, what the training criterion
    asks for) at the training cell's head: 2 x 4,095 rows of hidden 2048,
    walked in four chunks of 2 x 1,024 (each sequence padded by one row),
    vocabulary 92,544, bf16. The chip's compiler keeps three products
    (logits, dx, dW) where the per-token path has four, no gather or
    scatter (the label is a select over the chunk's columns) and no array
    of rows x vocabulary: the float32 arrays of the vocabulary's width are
    the one dW accumulator and one chunk's logits. The temporaries read
    2,308,231,680 B (2.15 GiB) in both layouts: the accumulator and a
    chunk's logits (0.71 GiB each), the bf16 dW (0.35) and the rows' dx;
    the bound leaves 4.7 % over that."""
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    b, t, h, v = 2, 4095, 2048, 92544
    one_chip = SingleDeviceSharding(topo.devices[0])

    def loss(x, w, lab):
        return fused_linear_cross_entropy(x, w, lab, transpose_y=transpose_y,
                                          reduction="mean")

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
            ((b, t, h), BF16), ((v, h) if transpose_y else (h, v), BF16),
            ((b, t), jnp.int32))]).compile()
    ops = _hlo_ops(compiled.as_text())
    products = [dims for _, dims, op in ops if op == "convolution"]
    assert sorted(products) == sorted(
        [(2048, v), (v, h) if transpose_y else (h, v), (2048, h)]), products
    assert not [op for _, _, op in ops if op in ("gather", "scatter")]
    assert not [dims for _, dims, op in ops
                if op == "dynamic-update-slice" and v in dims]
    wide = {dims for dt, dims, _ in ops if dt == "f32" and v in dims}
    assert wide <= {(2048, v), (v, h), (h, v)}, wide
    assert compiled.memory_analysis().temp_size_in_bytes < 2.25 * 2 ** 30


def test_fused_lm_head_mean_keeps_a_data_parallel_batch_on_its_devices(topo):
    """The mean path under data parallelism: the training cell's head with
    a batch of 8 x 4,095 rows sharded over the four chips of a v5e:2x2 and
    the (H, V) weight replicated. Each chunk takes its rows from every
    sequence, so no chip gathers another's rows: every product is one
    chip's quarter of a chunk (512 of 2,048 rows), and the one
    collective of the weight's size is the all-reduce of the float32 dW
    accumulator, once, after the walk (its loop body holds none)."""
    import re

    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    b, t, h, v = 8, 4095, 2048, 92544
    mesh = Mesh(np.array(topo.devices).reshape(4), ("dp",))

    def loss(x, w, lab):
        return fused_linear_cross_entropy(x, w, lab, transpose_y=False,
                                          reduction="mean")

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=NamedSharding(mesh, spec))
          for s, d, spec in (((b, t, h), BF16, P("dp")), ((h, v), BF16, P()),
                             ((b, t), jnp.int32, P("dp")))]).compile()
    text = compiled.as_text()
    ops = _hlo_ops(text)
    assert not [op for _, _, op in ops if op.startswith("all-gather")]
    assert sorted(dims for _, dims, op in ops if op == "convolution") == \
        sorted([(512, v), (h, v), (512, h)])
    reduced = [dims for _, dims, op in ops if op.startswith("all-reduce")
               and v in dims]
    assert reduced == [(h, v)], reduced
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert re.search(rf"f32\[{h},{v}\]\S* all-reduce(-start)?\(", entry)


@pytest.mark.parametrize("b", [1, 32], ids=["width1", "width32"])
def test_flash_attention_paged_compiles_at_the_chat_cell_shape(topo, b):
    """The chunk / final / resume programs' attention: 128 new tokens a
    row, 32 query / 8 KV heads, the 513-page pool read through a table of
    32 attention-visible columns. The pool goes in as it is: no copy of
    it may appear beside the kernel."""
    pool = ((513, 128, 8, 128), BF16)
    compiled = _compile(flash_attention_paged, topo,
                        ((b, 128, 32, 128), BF16), pool, pool,
                        ((b, 32), jnp.int32), ((b,), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * b * 2 ** 20


def test_rms_norm_and_fused_rope_compile_at_hidden_4096(topo):
    def norm_loss(x, w):
        return rms_norm(x, w, jnp.zeros_like(w), 1e-6, False).astype(
            jnp.float32).sum()

    _compile(jax.grad(norm_loss, argnums=(0, 1)), topo,
             ((2, 1024, 4096), BF16), ((4096,), BF16))

    def rope_loss(q, k, cos, sin):
        oq, ok = fused_rope(q, k, cos, sin)
        return (oq.astype(jnp.float32).sum()
                + ok.astype(jnp.float32).sum())

    _compile(jax.grad(rope_loss, argnums=(0, 1)), topo,
             ((2, 1024, 32, 128), BF16), ((2, 1024, 32, 128), BF16),
             ((1024, 128), jnp.float32), ((1024, 128), jnp.float32))


def test_kernels_partition_over_a_four_chip_mesh(topo):
    """GSPMD cannot partition a Mosaic kernel; under ``kernel_mesh`` the
    attention family is partitioned by ``shard_map`` instead — heads over
    the TP axis for serving, batch x heads for the dp x mp train step."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))

    def aval(shape, dtype, *spec, mesh=mesh):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    # serving splits heads over the TP axis; every row is on every chip.
    # Second case: the chat cell's shape as TPShardedEngine lays it over
    # four chips, 8 query / 2 KV heads a chip
    for over, b, kv_heads, pages, cols in (
            (mesh, 4, 32, 64, 8),
            (Mesh(np.array(topo.devices), ("mp",)), 32, 8, 513, 32)):
        def decode(q, kp, vp, tables, lens):
            with pallas.kernel_mesh(over, head_axis="mp"):
                return paged_attention(q, kp, vp, tables, lens)

        pool = aval((pages, 128, kv_heads, 128), BF16, None, None, "mp",
                    None, mesh=over)
        text = jax.jit(decode).lower(
            aval((b, 32, 128), BF16, None, "mp", None, mesh=over), pool,
            pool, aval((b, cols), jnp.int32, mesh=over),
            aval((b,), jnp.int32, mesh=over)).compile().as_text()
        assert MOSAIC_CALL in text

        def chunk(q, kp, vp, tables, bases):
            with pallas.kernel_mesh(over, head_axis="mp"):
                return flash_attention_paged(q, kp, vp, tables, bases)

        text = jax.jit(chunk).lower(
            aval((b, 128, 32, 128), BF16, None, None, "mp", None,
                 mesh=over), pool, pool,
            aval((b, cols), jnp.int32, mesh=over),
            aval((b,), jnp.int32, mesh=over)).compile().as_text()
        assert MOSAIC_CALL in text

    def train(q, k, v):
        with pallas.kernel_mesh(mesh, batch_axes=("dp",), head_axis="mp"):
            return flash_attention(q, k, v, is_causal=True).astype(
                jnp.float32).sum()

    qkv = aval((4, 1024, 32, 128), BF16, "dp", None, "mp", None)
    text = jax.jit(jax.grad(train, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    assert MOSAIC_CALL in text

    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(lambda q, k, v: flash_attention(q, k, v, is_causal=True)
                ).lower(qkv, qkv, qkv)
