"""Decode-serving attention — Pallas TPU kernels over a paged KV cache.

TPU-native re-emission of the reference's decode kernel pair:

* ``paged_attention`` — the analog of blocked/paged KV-cache attention
  (/root/reference/paddle/phi/kernels/fusion/gpu/
  block_multi_head_attention_kernel.cu): the KV cache lives in fixed-size
  pages shared by all sequences; a per-sequence block table maps logical
  cache positions to physical pages. The page indices ride as
  scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``) so each grid
  step's page DMA is issued from the block table before the body runs —
  the TPU shape of the CUDA kernel's gather-from-block-table.
* ``masked_decode_attention`` — the analog of masked decode MHA
  (masked_multihead_attention_kernel.cu): single-token queries attending
  over a fixed-size contiguous cache with a per-sequence valid length.
  Implemented as ``paged_attention`` on a trivially-paged view (the cache
  IS page i of a per-sequence table), so there is one kernel to tune.

Layouts: q (B, H, D) one decode token per sequence; pages
(num_pages, page_size, KV_HEADS, D); block_tables (B, pages_per_seq) int32;
lengths (B,) int32. GQA folds query-head groups onto kv heads in the index
map. Online softmax in f32; each (b, h) accumulates across its pages via
VMEM scratch carried over the innermost grid dim.
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

__all__ = ["paged_attention", "masked_decode_attention",
           "paged_attention_supported"]

NEG_INF = -1e30


def paged_attention_supported(q, k_pages):
    if q.ndim != 3 or k_pages.ndim != 4:
        return False
    h, kvh = q.shape[1], k_pages.shape[2]
    return h % kvh == 0 and q.shape[2] == k_pages.shape[3]


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, scale, page_size, pages_per_seq,
                   kvh):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[b]
    valid = p * page_size < length

    @pl.when(valid)
    def _accumulate():
        h, d = q_ref.shape[1], q_ref.shape[2]
        group = h // kvh
        q = q_ref[0, :, :].astype(jnp.float32) * scale        # (H, D)
        # per-kv-head 2-D matmuls, statically unrolled: Mosaic has no
        # mismatched-batch-dim dot, and sublane transposes of the page
        # block are far slower than kvh small matmuls
        s_parts = []
        for i in range(kvh):
            k_i = k_ref[0, :, i, :].astype(jnp.float32)       # (page, D)
            q_i = q[i * group:(i + 1) * group, :]             # (G, D)
            s_parts.append(jax.lax.dot_general(
                q_i, k_i, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))          # (G, page)
        s = jnp.concatenate(s_parts, axis=0)                  # (H, page)
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + p * page_size
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[:, :]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)                               # (H, page)
        l_ref[:, :] = alpha * l_ref[:, :] + jnp.sum(pr, axis=1,
                                                    keepdims=True)
        m_ref[:, :] = m_new
        pv_parts = []
        for i in range(kvh):
            v_i = v_ref[0, :, i, :].astype(jnp.float32)       # (page, D)
            pr_i = pr[i * group:(i + 1) * group, :]           # (G, page)
            pv_parts.append(jax.lax.dot_general(
                pr_i, v_i, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))          # (G, D)
        acc_ref[:, :] = alpha * acc_ref[:, :] + jnp.concatenate(
            pv_parts, axis=0)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        o_ref[0, :, :] = (
            acc_ref[:, :] / jnp.maximum(l_ref[:, :], 1e-30)
        ).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    pages_per_seq=None):
    """Single-token attention over a paged KV cache.

    q: (B, H, D); k_pages/v_pages: (num_pages, page_size, KVH, D);
    block_tables: (B, pages_per_seq) int32 physical page ids;
    lengths: (B,) int32 valid context length per sequence.
    Returns (B, H, D).

    ``pages_per_seq`` bounds how many table columns the grid walks per
    sequence (static slice). Dynamic serving tables are RAGGED: rows
    hold however many pages their slot was granted, padded with
    scratch-alias columns the kernel must not pay grid steps for — the
    per-page ``valid`` mask already skips DMA'd pages past ``lengths``,
    but the grid itself is static, so the caller caps it here.

    Block shapes keep the last two dims equal to full array dims
    ((H, D) for q/out, (KVH, D) for pages) — the Mosaic lowering
    requirement — so all query heads of one token are processed per grid
    step, with the per-(b) online-softmax state carried in VMEM scratch
    across the page dimension.
    """
    if (pages_per_seq is not None
            and pages_per_seq < block_tables.shape[1]):
        block_tables = block_tables[:, :pages_per_seq]
    return _over_mesh(
        _paged_attention,
        (q, k_pages, v_pages, block_tables.astype(jnp.int32),
         lengths.astype(jnp.int32)),
        ("bh.", "..h.", "..h.", "b.", "b"), "bh.")


def _paged_attention(q, k_pages, v_pages, block_tables, lengths):
    b, h, d = q.shape
    npages, page_size, kvh, _ = k_pages.shape
    pages_per_seq = block_tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    grid = (b, pages_per_seq)

    def q_map(bi, pi, tables, lens):
        return (bi, 0, 0)

    def kv_map(bi, pi, tables, lens):
        # Table tails past lengths[b] may be uninitialized in real paged
        # serving: redirect the (masked-anyway) DMA to the row's first page
        # and clamp into the pool, so garbage entries never address memory.
        pid = jnp.where(pi * page_size < lens[bi], tables[bi, pi],
                        tables[bi, 0])
        return (jnp.clip(pid, 0, npages - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, d), q_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, h, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),   # running max
            pltpu.VMEM((h, 1), jnp.float32),   # running denom
            pltpu.VMEM((h, d), jnp.float32),   # running numerator
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=page_size,
        pages_per_seq=pages_per_seq, kvh=kvh)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=_interpret(),
    )(block_tables, lengths, q, k_pages, v_pages)


def masked_decode_attention(q, k_cache, v_cache, lengths, page_size=None):
    """Decode attention over a contiguous per-sequence cache
    (masked_multihead_attention semantics).

    q: (B, H, D); k_cache/v_cache: (B, MAX_LEN, KVH, D); lengths: (B,).
    Views the cache as pages without copying: (B*MAX_LEN/page, page, KVH, D)
    with block table row i = the pages of sequence i.
    """
    b, max_len, kvh, d = k_cache.shape
    if page_size is None:
        page_size = min(max_len, 128)
        while max_len % page_size:  # largest divisor ≤ 128
            page_size -= 1
    if max_len % page_size:
        raise ValueError(f"max_len {max_len} not divisible by page size "
                         f"{page_size}")
    per_seq = max_len // page_size
    k_pages = k_cache.reshape(b * per_seq, page_size, kvh, d)
    v_pages = v_cache.reshape(b * per_seq, page_size, kvh, d)
    tables = (jnp.arange(b, dtype=jnp.int32)[:, None] * per_seq
              + jnp.arange(per_seq, dtype=jnp.int32)[None, :])
    return paged_attention(q, k_pages, v_pages, tables, lengths)
