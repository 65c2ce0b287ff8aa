"""Decode-serving attention — Pallas TPU kernels over a paged KV cache.

TPU-native re-emission of the reference's decode kernel pair:

* ``paged_attention`` — the analog of blocked/paged KV-cache attention
  (/root/reference/paddle/phi/kernels/fusion/gpu/
  block_multi_head_attention_kernel.cu): the KV cache lives in fixed-size
  pages shared by all sequences; a per-sequence block table maps logical
  cache positions to physical pages. The kernel walks a WORK LIST of the
  pages that hold tokens, built from ``lengths`` and the block table with
  a few XLA ops and handed over as scalar-prefetch operands
  (``pltpu.PrefetchScalarGridSpec``), so each grid step's page DMA is
  issued before the body runs — the TPU shape of the CUDA kernel's
  gather-from-block-table — and a call costs what its live pages cost,
  not what the table could hold.
* ``masked_decode_attention`` — the analog of masked decode MHA
  (masked_multihead_attention_kernel.cu): single-token queries attending
  over a fixed-size contiguous cache with a per-sequence valid length.
  Implemented as ``paged_attention`` on a trivially-paged view (the cache
  IS page i of a per-sequence table), so there is one kernel to tune.

Layouts: q (B, H, D) one decode token per sequence; pages
(num_pages, page_size, KV_HEADS, D); block_tables (B, pages_per_seq) int32;
lengths (B,) int32. The grid is one-dimensional over the work list, its
bound the number of live pages (a dynamic grid dimension under the static
ceiling B * pages_per_seq); a row's pages are consecutive items in table
order, so each (b) accumulates across its pages via VMEM scratch (online
softmax in f32), initialised at the row's first page and written out at
its last. GQA folds query-head groups onto kv heads inside the body.
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


def _decode_kernel(rows_ref, pages_ref, phys_ref, lens_ref, q_ref, k_ref,
                   v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, page_size,
                   kvh):
    item = pl.program_id(0)
    b = rows_ref[item]
    p = pages_ref[item]
    length = lens_ref[b]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # false only on the one item a row of length 0 holds
    @pl.when(p * page_size < length)
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

    @pl.when((p + 1) * page_size >= length)
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

    ``pages_per_seq`` is the static ceiling of the table's width (a
    static slice): dynamic serving tables carry trailing write-scratch
    columns that attention never reads. Inside it the kernel does work
    in proportion to ``sum(ceil(lengths / page_size))``: the grid walks
    the pages that hold tokens and no others, a row of length 0 yields
    zeros, and a table entry past ``lengths[b]`` is never attended to
    nor used as an address outside the pool.

    Block shapes keep the last two dims equal to full array dims
    ((H, D) for q/out, (KVH, D) for pages) — the Mosaic lowering
    requirement — so all query heads of one token are processed per grid
    step, with the per-(b) online-softmax state carried in VMEM scratch
    across the row's pages.
    """
    if (pages_per_seq is not None
            and pages_per_seq < block_tables.shape[1]):
        block_tables = block_tables[:, :pages_per_seq]
    return _over_mesh(
        _paged_attention,
        (q, k_pages, v_pages, block_tables.astype(jnp.int32),
         lengths.astype(jnp.int32)),
        ("bh.", "..h.", "..h.", "b.", "b"), "bh.")


def _work_list(block_tables, lengths, page_size, npages):
    """The (row, page) pairs that hold tokens, row-major, as three int32
    vectors of the static ceiling ``b * pages_per_seq`` — each item's row,
    its page's column in the row's table and that page's id in the pool —
    and how many of them are real. A row of length 0 still gets one item:
    it attends to nothing, but its output block has to be written. Table
    entries are clamped into the pool, so a garbage entry (only such a
    row's first can be reached) never addresses memory outside it."""
    b, pages_per_seq = block_tables.shape
    counts = jnp.maximum(pl.cdiv(lengths, page_size), 1)
    ends = jnp.cumsum(counts)
    item = jnp.arange(b * pages_per_seq, dtype=jnp.int32)
    done = item[:, None] >= ends[None, :]   # the rows wholly before an item
    rows = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), b - 1)
    pages = jnp.minimum(
        item - jnp.sum(jnp.where(done, counts[None, :], 0), axis=1),
        pages_per_seq - 1)
    phys = jnp.clip(block_tables[rows, pages], 0, npages - 1)
    return rows, pages, phys, ends[-1]


def _paged_attention(q, k_pages, v_pages, block_tables, lengths):
    b, h, d = q.shape
    npages, page_size, kvh, _ = k_pages.shape
    scale = 1.0 / math.sqrt(d)
    # the table's width is the static ceiling of a row's pages
    lengths = jnp.minimum(lengths, block_tables.shape[1] * page_size)
    # built here, inside over_mesh's shard_map, from the shard's own rows
    rows, pages, phys, total = _work_list(block_tables, lengths, page_size,
                                          npages)

    def q_map(i, rows, pages, phys, lens):
        return (rows[i], 0, 0)

    def kv_map(i, rows, pages, phys, lens):
        return (phys[i], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total,),
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
        _decode_kernel, scale=scale, page_size=page_size, kvh=kvh)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="paged_attention",
        interpret=_interpret(),
    )(rows, pages, phys, lengths, q, k_pages, v_pages)


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
