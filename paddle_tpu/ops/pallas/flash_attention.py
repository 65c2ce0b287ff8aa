"""Flash attention — Pallas TPU kernel, forward + backward.

The TPU-native re-emission of the reference's FA2 integration
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu:587, which
dynloads libflashattn.so) and of the fused attention kernel family
(paddle/phi/kernels/fusion/gpu/fused_attention_kernel.cu:40): tiled online-
softmax attention that never materializes the (S, S) score matrix in HBM.

Layout: (B, S, H, D) at the public boundary (matching the reference's
flash_attn), transposed to (B, H, S, D) for the kernel. Block sizes are
MXU/VPU aligned (q/k blocks of 128 rows); accumulation is f32; the backward
is the standard two-kernel FA2 split (dkdv over k-blocks, dq over q-blocks)
with the usual ``delta = rowsum(dO * O)`` trick.

What each grid keeps resident. The forward and dq grids are
``(b, h, q block)``: a head's whole-sequence K and V blocks keep their block
index across its q blocks and are fetched once a head. The dk/dv grid is
``(b, query head, k block)`` with the k block fastest, for the same reason
from the other side: the head's whole-sequence q, do, lse and delta blocks
(1 + 1 + 2 + 2 MiB at 4096 x 128, the two f32 columns padded to 128 lanes;
12 MiB double-buffered) stay put for its 16 k blocks, and lse / delta are
turned from columns into rows once a head. Its body works in the
orientation its outputs have (``s^T = k q^T``, ``dp^T = v do^T``), so
``dv += p^T do`` and ``dk += ds^T q`` contract no tile over its first axis.
Under GQA every query head writes an f32 partial ``(b, h, sk, d)`` and one
XLA reduction sums a kv head's group (never through bf16). The k blocks
are walked last to first, so that under the causal mask a head's last step
is its longest and hides the next head's fetch. Measured on v5e at the
``internlm2-d12-pretrain-1chip`` shape (2, 4096, 16 / 8 heads of 128,
causal, bf16; PERF.md section 6, PR 28), kernel alone, ms a call: 4.92 with
the group fastest and the score tile transposed twice a product step; 5.19
with the grid reordered alone (the re-fetch of 6 MiB a step had been hidden
behind the transposes); 3.25 without the transposes; 3.02 with the k blocks
last to first: 0.76 ms a 256x256x128 product against dq's 0.73, dk and dv
bit-identical throughout.

Masking (round 4, the flash_attn varlen/padding analog): per-sequence
valid lengths and/or segment ids are folded into per-token int32 segment
arrays (padding becomes segment ``-1``); the kernels mask score entries
where the q and k segments differ, fwd + both bwd passes. Fully-masked
(padding) query rows produce finite garbage and their lse is degenerate —
harmless because any loss masks those rows, making their upstream
gradient zero, which zeroes every ds contribution through them.

Prefill over a paged cache (``flash_attention_paged``, forward only): the
new tokens of a chunk, or of the tail behind a prefix hit, attend over
their row's pages through the block table, each row bounded by its own
base. Its grid is ``paged_attention``'s work list (the pages that hold
``base + S`` columns, row / page / pool id and the bases as scalar
prefetch), one (row, page) a step with every head in it, so a call costs
what the rows hold and not what the table could: measured on v5e at the
``mistral7b-chat-open`` shape (128 new tokens, 32 / 8 heads of 128, page
128; PERF.md section 6, PR 30) ~6 us a live page, against 0.42 ms a row
for the masked composition over all 4,096 columns that it replaced in the
engine's chunk programs. Routed from ``models.llama.cached_attention``.

Gating (ops/nn_kernels.py): FLAGS_use_pallas_kernels on TPU, no dense
attn_mask, no dropout, seq divisible by the block size; otherwise the XLA
sdpa composition runs (with a one-time fallback warning).
``interpret=True`` is used automatically off-TPU so CI exercises the same
code path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from . import over_mesh as _over_mesh
from .decode_attention import _work_list

__all__ = ["flash_attention", "flash_attention_supported", "build_segments",
           "flash_attention_paged", "flash_attention_paged_supported"]

BLOCK_Q = 128  # minimum/gating granularity
BLOCK_K = 128
# Measured on v5e at (4, 1536, 12, 128), before PR 28's dk/dv kernel: 256x256
# blocks run the fwd+bwd in 5.2ms vs 11.8ms at 128x128 (VMEM reuse sweet
# spot); 512x512 regresses. All three kernels take this tile; the dk/dv
# kernel's cost per product at it is in the module docstring.
PREFERRED_BLOCK = 256
NEG_INF = -1e30


def _block_for(seq: int) -> int:
    from ...core.flags import flag

    preferred = int(flag("FLAGS_flash_attention_block_size") or PREFERRED_BLOCK)
    return preferred if seq % preferred == 0 else BLOCK_Q


def flash_attention_supported(q, k, v, attn_mask=None, dropout_p=0.0):
    """Whether the Pallas path can serve this call."""
    if attn_mask is not None or dropout_p > 0.0:
        return False
    if q.ndim != 4:
        return False
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if sq % BLOCK_Q or sk % BLOCK_K:
        return False
    if d > 256:
        return False
    if h % k.shape[2]:  # GQA: q heads must group evenly onto kv heads
        return False
    return True


# ------------------------------------------------------------------ forward

def _fwd_kernel(*refs, scale, causal, block_k, seq_k, seq_q, masked):
    if masked:
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref), qseg_ref, kseg_ref = refs, None, None
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32) * scale  # (bq, d)
    bq = q.shape[0]
    d = q.shape[1]
    qseg = (qseg_ref[0, 0, pl.ds(qi * bq, bq)] if masked
            else None)  # (bq,)

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    num_k = seq_k // block_k
    # causal with cache offset (sq < sk attends the full prefix): row r sees
    # cols <= r + (seq_k - seq_q). Exact ceil bound, valid for bq != bk.
    off = seq_k - seq_q
    num_k_eff = (jnp.minimum(
        num_k, ((qi + 1) * bq + off + block_k - 1) // block_k)
        if causal else num_k)

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        if masked:
            kseg = kseg_ref[0, 0, pl.ds(ki * block_k, block_k)]  # (bk,)
            s = jnp.where(qseg[:, None] == kseg[None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k_eff, body, (m0, l0, acc0))
    o_ref[0, 0, :, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[0, 0, :, :] = m + jnp.log(jnp.maximum(l, 1e-30))


def _fwd(q, k, v, causal, scale, q_seg=None, k_seg=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    group = h // k.shape[1]  # GQA: q heads per kv head (1 = MHA)
    BQ = _block_for(sq)
    BK = _block_for(sk)
    grid = (b, h, sq // BQ)
    masked = q_seg is not None
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_k=BK, seq_k=sk,
        seq_q=sq, masked=masked)
    in_specs = [
        pl.BlockSpec((1, 1, BQ, d), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda b_, h_, i: (b_, h_ // group, 0, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda b_, h_, i: (b_, h_ // group, 0, 0)),
    ]
    operands = [q, k, v]
    if masked:
        in_specs += [
            pl.BlockSpec((1, 1, sq), lambda b_, h_, i: (b_, 0, 0)),
            pl.BlockSpec((1, 1, sk), lambda b_, h_, i: (b_, 0, 0)),
        ]
        operands += [q_seg, k_seg]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, BQ, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, BQ, 1), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        name="flash_fwd",
        interpret=_interpret(),
    )(*operands)
    return out, lse


# ------------------------------------------- forward over a paged cache

def flash_attention_paged_supported(q, k_pages):
    """Whether :func:`flash_attention_paged` can serve ``q`` (B, S, H, D)
    over pages (P, page, KVH, D): the flash route's own test (head size,
    block multiples, GQA divisibility) with the page as the key block."""
    if q.ndim != 4 or k_pages.ndim != 4:
        return False
    _, sq, h, d = q.shape
    _, page, kvh, dk = k_pages.shape
    return (sq % BLOCK_Q == 0 and page % BLOCK_K == 0 and d == dk
            and d <= 256 and h % kvh == 0)


def _fwd_paged_kernel(rows_ref, pages_ref, phys_ref, bases_ref, q_ref, k_ref,
                      v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
                      page_size, kvh, max_cols):
    """One (row, page) of the work list: every query head of the row's
    ``sq`` new tokens against one page of its cache. Query ``r`` of row
    ``b`` sits at position ``bases[b] + r`` and sees columns up to it. A
    kv head's group is one (group * sq, d) operand against its (page, d)
    slice of the page, as ``paged_attention`` slices it."""
    item = pl.program_id(0)
    b = rows_ref[item]
    p = pages_ref[item]
    base = bases_ref[b]
    h, sq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    group = h // kvh
    n = group * sq

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the same for every head: column p * page + c is seen by query r when
    # it is <= base + r (the chunk's own keys are in the page already)
    cols = jax.lax.broadcasted_iota(jnp.int32, (sq, page_size), 1)
    rows = jax.lax.broadcasted_iota(jnp.int32, (sq, page_size), 0)
    seen = jnp.concatenate(
        [cols + p * page_size <= rows + base] * group, axis=0)  # (n, page)
    for i in range(kvh):
        heads = slice(i * group, (i + 1) * group)
        state = slice(i * n, (i + 1) * n)
        q = q_ref[0, heads, :, :].reshape(n, d)
        s = jax.lax.dot_general(
            q, k_ref[0, :, i, :].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (n, page)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_ref[state, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)
        l_ref[state, :] = alpha * l_ref[state, :] + jnp.sum(
            pr, axis=1, keepdims=True)
        m_ref[state, :] = m_new
        acc_ref[state, :] = alpha * acc_ref[state, :] + jax.lax.dot_general(
            pr, v_ref[0, :, i, :].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when((p + 1) * page_size >= jnp.minimum(base + sq, max_cols))
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, :, :] = out.reshape(h, sq, d).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_paged(q, k_pages, v_pages, block_tables, bases, interpret):
    # jitted: a program that calls it once a layer traces and lowers the
    # kernel once, not once a layer
    b, h, sq, d = q.shape
    npages, page_size, kvh, _ = k_pages.shape
    max_cols = block_tables.shape[1] * page_size
    # a row's work: the pages that hold its base + sq columns, inside the
    # table's width (a final chunk's padded tail may reach past it)
    rows, pages, phys, total = _work_list(
        block_tables, jnp.minimum(bases + sq, max_cols), page_size, npages)

    def q_map(i, rows, pages, phys, bases):
        return (rows[i], 0, 0, 0)

    def kv_map(i, rows, pages, phys, bases):
        return (phys[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total,),
        in_specs=[
            pl.BlockSpec((1, h, sq, d), q_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, sq, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((h * sq, 1), jnp.float32),   # running max
            pltpu.VMEM((h * sq, 1), jnp.float32),   # running denom
            pltpu.VMEM((h * sq, d), jnp.float32),   # running numerator
        ],
    )
    kernel = functools.partial(
        _fwd_paged_kernel, scale=1.0 / math.sqrt(d), page_size=page_size,
        kvh=kvh, max_cols=max_cols)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        name="flash_fwd_paged",
        interpret=interpret,
    )(rows, pages, phys, bases, q, k_pages, v_pages)


def flash_attention_paged(q, k_pages, v_pages, block_tables, bases):
    """Causal attention of ``sq`` NEW tokens a row over that row's paged
    cache (prefill over a cache: a chunk of a long prompt, the tail behind
    a prefix hit). No gradient.

    q: (B, S, H, D), the new tokens' queries; k_pages / v_pages:
    (num_pages, page_size, KVH, D), the pool, which already holds the new
    tokens' keys; block_tables: (B, pages_per_seq) int32, the columns
    attention may read (a final chunk's padded tail that reaches past them
    sees them all); bases: (B,) int32, the position of each row's first
    new token, page-aligned or not. Query ``r`` of row ``b`` sees cache
    columns ``<= bases[b] + r``. Returns (B, S, H, D).

    The grid is ``paged_attention``'s work list with ``bases + S`` as the
    lengths: the pages that hold those columns and no others, read through
    the block table (no gather), so a call costs what ``bases + S`` costs
    and not what the table could hold."""
    out = _over_mesh(
        functools.partial(_fwd_paged, interpret=_interpret()),
        (jnp.swapaxes(q, 1, 2), k_pages, v_pages,
         block_tables.astype(jnp.int32), bases.astype(jnp.int32)),
        ("bh..", "..h.", "..h.", "b.", "b"), "bh..")
    return jnp.swapaxes(out, 1, 2)


# ------------------------------------------------------------------ backward

def _bwd_dkdv_kernel(*refs, scale, causal, block_q, seq_q, seq_k, masked):
    """One (query head, k block) of dk / dv. Everything is computed in the
    orientation the outputs need: ``s^T = k q^T`` and ``dp^T = v do^T`` are
    (bk, bq), so ``dv += p^T do`` and ``dk += ds^T q`` are plain products and
    no score tile is transposed. ``lse`` / ``delta`` arrive as columns
    (sq, 1) and are wanted as rows (1, bq): they are turned once a head,
    on its first grid step, into the two row scratches."""
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dk_ref, dv_ref, lse_row, dlt_row) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref, lse_row, dlt_row) = refs
        qseg_ref = kseg_ref = None
    step = pl.program_id(2)
    # s and dp take the operands as they came: a bf16 x bf16 product summed
    # in f32 is the product of their f32 casts (mixed dtypes meet in f32)
    op_dtype = jnp.result_type(q_ref.dtype, k_ref.dtype, v_ref.dtype,
                               do_ref.dtype)
    k = k_ref[0, 0, :, :].astype(op_dtype)  # (bk, d)
    v = v_ref[0, 0, :, :].astype(op_dtype)
    bk, d = k.shape
    num_q = seq_q // block_q
    ki = seq_k // bk - 1 - step  # k blocks are walked last to first (_bwd)

    def q_rows(qi):
        return pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    # the k block is the fastest grid dimension: the head's q-side blocks
    # (and these rows) stay put while it runs
    @pl.when(step == 0)
    def _rows():
        def turn(qi, _):
            for col_ref, row_ref in ((lse_ref, lse_row), (delta_ref, dlt_row)):
                col = col_ref[0, 0, q_rows(qi), :]  # (bq, 1)
                # over the 128 lanes, so that the transpose is tile-aligned
                row_ref[:, q_rows(qi)] = jnp.transpose(
                    jnp.broadcast_to(col, (block_q, 128)))[:8]
            return 0

        jax.lax.fori_loop(0, num_q, turn, 0)

    kseg = (kseg_ref[0, 0, pl.ds(ki * bk, bk)][:, None] if masked
            else None)  # (bk, 1)
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    off = seq_k - seq_q
    # causal: q rows with r + off < ki*bk see nothing of this k block
    q_start = jnp.maximum(ki * bk - off, 0) // block_q if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, q_rows(qi), :].astype(op_dtype)  # (bq, d)
        do = do_ref[0, 0, q_rows(qi), :].astype(op_dtype)
        lse = lse_row[0:1, q_rows(qi)]  # (1, bq)
        dlt = dlt_row[0:1, q_rows(qi)]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bk, bq)
        if causal:
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + ki * bk
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + qi * block_q
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        if masked:
            qseg = qseg_ref[0, 0, q_rows(qi)]
            s = jnp.where(kseg == qseg[None, :], s, NEG_INF)
        # p and ds are f32 values and stay f32 operands
        p = jnp.exp(s - lse)  # p^T
        dv_new = dv + jax.lax.dot_general(
            p, do.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bk, bq)
        ds = p * (dp - dlt) * scale  # ds^T
        dk_new = dk + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(q_start, num_q, body, (dk0, dv0))
    dk_ref[0, 0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, block_k, seq_k, seq_q, masked):
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dq_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref) = refs
        qseg_ref = kseg_ref = None
    qi = pl.program_id(2)
    q = q_ref[0, 0, :, :].astype(jnp.float32)
    do = do_ref[0, 0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :, :]
    dlt = delta_ref[0, 0, :, :]
    bq, d = q.shape
    qseg = (qseg_ref[0, 0, pl.ds(qi * bq, bq)] if masked
            else None)  # (bq,)

    dq0 = jnp.zeros((bq, d), jnp.float32)
    num_k = seq_k // block_k
    off = seq_k - seq_q
    num_k_eff = (jnp.minimum(
        num_k, ((qi + 1) * bq + off + block_k - 1) // block_k)
        if causal else num_k)

    def body(ki, dq):
        k = k_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * block_k
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        if masked:
            kseg = kseg_ref[0, 0, pl.ds(ki * block_k, block_k)]
            s = jnp.where(qseg[:, None] == kseg[None, :], s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - dlt) * scale
        return dq + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_k_eff, body, dq0)
    dq_ref[0, 0, :, :] = dq.astype(dq_ref.dtype)


def _bwd(causal, scale, res, g):
    q, k, v, q_seg, k_seg, out, lse = res
    do = g
    b, h, sq, d = q.shape
    sk = k.shape[2]
    kvh = k.shape[1]
    group = h // kvh  # GQA: dk/dv accumulate over each kv head's group
    masked = q_seg is not None
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)

    BQ = _block_for(sq)
    BK = _block_for(sk)
    # grid: the k block is the FASTEST dim under a fixed query head, so the
    # head's whole-sequence q / do / lse / delta blocks are fetched once a
    # head, not once a step; a kv head's group then cannot share an output
    # block (its revisits would not be consecutive): each query head writes
    # its own f32 partial and the group is summed below. k blocks are walked
    # last to first: under the causal mask the first one sees every q block,
    # and as a head's LAST step it hides the next head's 6 MiB fetch, which
    # the last one (one q block) cannot
    num_k = sk // BK

    def q_side(b_, h_, i):
        return (b_, h_, 0, 0)

    def k_side(b_, h_, i):
        return (b_, h_ // group, num_k - 1 - i, 0)

    def out_block(b_, h_, i):
        return (b_, h_, num_k - 1 - i, 0)

    dkdv_in_specs = [
        pl.BlockSpec((1, 1, sq, d), q_side),
        pl.BlockSpec((1, 1, BK, d), k_side),
        pl.BlockSpec((1, 1, BK, d), k_side),
        pl.BlockSpec((1, 1, sq, d), q_side),
        pl.BlockSpec((1, 1, sq, 1), q_side),
        pl.BlockSpec((1, 1, sq, 1), q_side),
    ]
    dkdv_operands = [q, k, v, do, lse, delta]
    if masked:
        dkdv_in_specs += [
            pl.BlockSpec((1, 1, sq), lambda b_, h_, i: (b_, 0, 0)),
            pl.BlockSpec((1, 1, sk), lambda b_, h_, i: (b_, 0, 0)),
        ]
        dkdv_operands += [q_seg, k_seg]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=BQ, seq_q=sq, seq_k=sk, masked=masked),
        grid=(b, h, num_k),
        in_specs=dkdv_in_specs,
        out_specs=[pl.BlockSpec((1, 1, BK, d), out_block)] * 2,
        out_shape=[
            # GQA (group>1): f32 partials, one a query head, so the sum
            # over the group never rounds through bf16; MHA keeps the
            # input dtype (no partials, no extra HBM footprint)
            jax.ShapeDtypeStruct((b, h, sk, d),
                                 jnp.float32 if group > 1 else k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d),
                                 jnp.float32 if group > 1 else v.dtype),
        ],
        # lse and delta as rows: 8 sublanes is the least an f32 tile holds
        scratch_shapes=[pltpu.VMEM((8, sq), jnp.float32)] * 2,
        name="flash_bwd_dkdv",
        interpret=_interpret(),
    )(*dkdv_operands)
    if group > 1:
        dk = dk.reshape(b, kvh, group, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, kvh, group, sk, d).sum(axis=2).astype(v.dtype)

    dq_in_specs = [
        pl.BlockSpec((1, 1, BQ, d), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda b_, h_, i: (b_, h_ // group, 0, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda b_, h_, i: (b_, h_ // group, 0, 0)),
        pl.BlockSpec((1, 1, BQ, d), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, BQ, 1), lambda b_, h_, i: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, BQ, 1), lambda b_, h_, i: (b_, h_, i, 0)),
    ]
    dq_operands = [q, k, v, do, lse, delta]
    if masked:
        dq_in_specs += [
            pl.BlockSpec((1, 1, sq), lambda b_, h_, i: (b_, 0, 0)),
            pl.BlockSpec((1, 1, sk), lambda b_, h_, i: (b_, 0, 0)),
        ]
        dq_operands += [q_seg, k_seg]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=BK, seq_k=sk, seq_q=sq, masked=masked),
        grid=(b, h, sq // BQ),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, 1, BQ, d),
                               lambda b_, h_, i: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(*dq_operands)

    return dq, dk, dv, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_bhsd(q, k, v, q_seg, k_seg, causal, scale):
    out, _ = _fwd(q, k, v, causal, scale, q_seg, k_seg)
    return out


def _flash_fwd_rule(q, k, v, q_seg, k_seg, causal, scale):
    q, k, v, out, lse = _name_residuals(q, k, v, causal, scale, q_seg, k_seg)
    return out, (q, k, v, q_seg, k_seg, out, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


def build_segments(b, sq, sk, seq_lens=None, segment_ids=None):
    """Fold per-sequence valid lengths and/or packed-segment ids into the
    (B, S) int32 q/k segment arrays the kernels mask with. Padding positions
    get segment ``-1`` (so they only match other padding of the same row).
    ``segment_ids`` may be one (B, S) array (shared, requires sq == sk) or a
    (q_ids, k_ids) pair. Returns (q_seg, k_seg) or (None, None)."""
    if seq_lens is None and segment_ids is None:
        return None, None
    if segment_ids is not None:
        if isinstance(segment_ids, (tuple, list)):
            q_seg = jnp.asarray(segment_ids[0], jnp.int32)
            k_seg = jnp.asarray(segment_ids[1], jnp.int32)
        else:
            if sq != sk:
                raise ValueError(
                    f"a single shared segment_ids array requires sq == sk "
                    f"(got sq={sq}, sk={sk}); pass a (q_ids, k_ids) pair "
                    f"for cross-attention")
            ids = jnp.asarray(segment_ids, jnp.int32)
            q_seg = k_seg = ids
    else:
        q_seg = jnp.zeros((b, sq), jnp.int32)
        k_seg = q_seg if sq == sk else jnp.zeros((b, sk), jnp.int32)
    if seq_lens is not None:
        lens = jnp.asarray(seq_lens, jnp.int32)[:, None]
        q_seg = jnp.where(jnp.arange(q_seg.shape[1])[None, :] < lens,
                          q_seg, -1)
        k_seg = jnp.where(jnp.arange(k_seg.shape[1])[None, :] < lens,
                          k_seg, -1)
    return q_seg, k_seg


def flash_attention(q, k, v, is_causal=False, seq_lens=None,
                    segment_ids=None):
    """(B, S, H, D) flash attention. GQA-native: kv heads are NOT
    materialized to the query head count — the kernel index maps fold each
    query head onto its kv head (``h // group``), and the dk/dv pass
    writes one f32 partial a query head that is summed over the group, so
    KV memory/bandwidth stays at the grouped size.

    ``seq_lens`` (B,) int32 masks keys/queries past each row's valid length
    (the flash_attn padding/varlen analog,
    /root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu:587);
    ``segment_ids`` restricts attention to equal-id positions (packed
    sequences). Both compose with ``is_causal``. Outputs at padding rows
    are finite garbage — mask them in the loss."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    q_seg, k_seg = build_segments(b, sq, sk, seq_lens, segment_ids)
    if q_seg is not None:
        # (B, 1, S): full-row (1, 1, S) blocks satisfy the Mosaic
        # last-two-dims rule; kernels slice the row per block
        q_seg = q_seg[:, None, :]
        k_seg = k_seg[:, None, :]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    segs = () if q_seg is None else (q_seg, k_seg)

    def run(qh, kh, vh, *segs):
        q_seg, k_seg = segs or (None, None)
        return _flash_bhsd(qh, kh, vh, q_seg, k_seg, bool(is_causal), scale)

    out = _over_mesh(run, (qh, kh, vh) + segs,
                     ("bh..",) * 3 + ("b..",) * len(segs), "bh..")
    return jnp.swapaxes(out, 1, 2)


# ------------------------------------------- residuals a checkpoint may keep
# At the END of the file on purpose: a Mosaic module carries the line numbers
# of its kernel body and of the frames that called it, so a line added above
# ``flash_attention`` would change the text (and the compile-cache key) of
# every serving program that prefills through ``flash_fwd``.

from jax.ad_checkpoint import checkpoint_name  # noqa: E402

FLASH_OUT_NAME = "flash_out"
FLASH_LSE_NAME = "flash_lse"
FLASH_Q_NAME = "flash_q"
FLASH_K_NAME = "flash_k"
FLASH_V_NAME = "flash_v"
FLASH_RESIDUAL_NAMES = (FLASH_Q_NAME, FLASH_K_NAME, FLASH_V_NAME,
                        FLASH_OUT_NAME, FLASH_LSE_NAME)
__all__ += ["FLASH_OUT_NAME", "FLASH_LSE_NAME", "FLASH_Q_NAME",
            "FLASH_K_NAME", "FLASH_V_NAME", "FLASH_RESIDUAL_NAMES"]


def _name_residuals(q, k, v, causal, scale, q_seg, k_seg):
    """The forward rule's ``_fwd`` with the five arrays ``_bwd`` reads under
    names a checkpoint policy can keep: q, k and v as the kernel takes them
    (``(b, h, s, d)``, rope applied; the named values are the ones ``_fwd``
    reads AND the ones in the residual tuple), ``out`` and ``lse`` as it
    writes them. ``fleet.recompute`` keeps all five, so a recomputed block
    rebuilds nothing the attention's backward reads: no second ``flash_fwd``
    (PR 32), and no second q / k / v product, rope or swap to ``(b, h, s,
    d)`` (PR 34). Kept bytes a layer: tokens x (2 x q heads + 2 x kv heads)
    x head_dim elements for q, k, v and ``out``, plus ``lse``, an
    ``f32[b, h, s, 1]`` that the chip pads to 128 lanes (at 2 x 4096 tokens,
    16 / 8 heads of 128, bf16: 32 + 16 + 16 + 32 + 64 = 160 MiB). Outside
    such a policy a name is the identity and lowers to nothing. Returns
    ``(q, k, v, out, lse)``."""
    q = checkpoint_name(q, FLASH_Q_NAME)
    k = checkpoint_name(k, FLASH_K_NAME)
    v = checkpoint_name(v, FLASH_V_NAME)
    out, lse = _fwd(q, k, v, causal, scale, q_seg, k_seg)
    return (q, k, v, checkpoint_name(out, FLASH_OUT_NAME),
            checkpoint_name(lse, FLASH_LSE_NAME))
