"""Decode attention over a paged LATENT cache (multi-head latent attention).

The cache of a latent-attention layer holds, a token, one compressed vector
``c`` (the normed key/value latent, width C) and one rotated rope key ``r``
(width R) that every query head shares. In the absorbed form a decode step
needs no per-head keys or values: head h's query is carried into the latent
space (``q_lat`` = q_nope W_k^T, width C), its score against a cached token
is ``q_lat . c + q_rope . r``, and its output is the softmax-weighted sum of
the ``c`` themselves, carried back out of the latent space by the caller.

``paged_mla_attention`` walks the same WORK LIST of live pages that
``decode_attention.paged_attention`` walks (one grid step a page that holds
tokens, scalar-prefetched row / column / pool id), with all H query heads
of a row against the page's one latent "head": scores from the C and the R
part, values are the C part. Layouts: q_lat (B, H, C), q_rope (B, H, R);
c_pages (num_pages, page, C), r_pages (num_pages, page, R): the page's
tokens lie on sublanes and the widths on lanes, so no dimension of 1 is
padded to a tile; block_tables (B, pages_per_seq) int32; lengths (B,).
``mla_attention_reference`` is the jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret
from .decode_attention import NEG_INF, _work_list

__all__ = ["paged_mla_attention", "mla_attention_reference"]


def _mla_kernel(rows_ref, pages_ref, phys_ref, lens_ref, ql_ref, qr_ref,
                c_ref, r_ref, o_ref, m_ref, l_ref, acc_ref, *, scale,
                page_size):
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
        c = c_ref[0, :, :]                                    # (page, C)
        # operands go to the MXU in the cache's own type (bf16 when
        # served), products accumulate in float32
        s = jax.lax.dot_general(
            ql_ref[0, :, :], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # (H, page)
        s = s + jax.lax.dot_general(
            qr_ref[0, :, :], r_ref[0, :, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s = s * scale
        pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + p * page_size
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[:, :]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)                               # (H, page)
        l_ref[:, :] = alpha * l_ref[:, :] + jnp.sum(pr, axis=1,
                                                    keepdims=True)
        m_ref[:, :] = m_new
        acc_ref[:, :] = alpha * acc_ref[:, :] + jax.lax.dot_general(
            pr.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (H, C)

    @pl.when((p + 1) * page_size >= length)
    def _finalize():
        o_ref[0, :, :] = (
            acc_ref[:, :] / jnp.maximum(l_ref[:, :], 1e-30)
        ).astype(o_ref.dtype)


def paged_mla_attention(q_lat, q_rope, c_pages, r_pages, block_tables,
                        lengths, scale, pages_per_seq=None):
    """One decode token a sequence against its paged latent cache.

    q_lat (B, H, C) and q_rope (B, H, R) in the cache's type; c_pages
    (num_pages, page, C), r_pages (num_pages, page, R); block_tables
    (B, pages_per_seq) int32; lengths (B,) int32 valid tokens a sequence
    (the token just written included). ``scale`` multiplies the summed
    score (1 / sqrt(nope + rope head size): the plain form's). Returns
    the latent outputs (B, H, C): sum_t softmax_t c_t. A row of length 0
    yields zeros; work is in proportion to the pages that hold tokens.
    """
    if (pages_per_seq is not None
            and pages_per_seq < block_tables.shape[1]):
        block_tables = block_tables[:, :pages_per_seq]
    b, h, c_w = q_lat.shape
    r_w = q_rope.shape[2]
    npages, page_size, _ = c_pages.shape
    block_tables = block_tables.astype(jnp.int32)
    lengths = jnp.minimum(lengths.astype(jnp.int32),
                          block_tables.shape[1] * page_size)
    rows, pages, phys, total = _work_list(block_tables, lengths, page_size,
                                          npages)

    def q_map(i, rows, pages, phys, lens):
        return (rows[i], 0, 0)

    def page_map(i, rows, pages, phys, lens):
        return (phys[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(total,),
        in_specs=[
            pl.BlockSpec((1, h, c_w), q_map),
            pl.BlockSpec((1, h, r_w), q_map),
            pl.BlockSpec((1, page_size, c_w), page_map),
            pl.BlockSpec((1, page_size, r_w), page_map),
        ],
        out_specs=pl.BlockSpec((1, h, c_w), q_map),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),     # running max
            pltpu.VMEM((h, 1), jnp.float32),     # running denominator
            pltpu.VMEM((h, c_w), jnp.float32),   # running numerator
        ],
    )
    kernel = functools.partial(_mla_kernel, scale=float(scale),
                               page_size=page_size)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c_w), q_lat.dtype),
        name="paged_mla_attention",
        interpret=_interpret(),
    )(rows, pages, phys, lengths, q_lat.astype(c_pages.dtype),
      q_rope.astype(r_pages.dtype), c_pages, r_pages)


_HEAD_BLOCK = 8


def latent_attend(q_lat, q_rope, c_all, r_all, mask, scale):
    """The absorbed form as a masked composition: q_lat (B, S, H, C),
    q_rope (B, S, H, R) against contiguous latents c_all (B, L, C) and rope
    keys r_all (B, L, R); ``mask`` (B or 1, 1, S, L), true where a query
    may look. Scores and softmax in float32. Returns (B, S, H, C) in
    q_lat's type. Chunked and resume prefill attend through this; so does
    decode where the kernel is off. A chunk's heads go eight at a time:
    the float32 scores of 32 rows x 32 heads x 128 x 4096 would be 2 GiB
    beside the weights."""
    b, s, h, _ = q_lat.shape

    def attend(ql, qr):
        sc = jnp.einsum("bshc,blc->bhsl", ql, c_all,
                        preferred_element_type=jnp.float32)
        sc = sc + jnp.einsum("bshr,blr->bhsl", qr, r_all,
                             preferred_element_type=jnp.float32)
        sc = jnp.where(mask, sc * scale, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1).astype(c_all.dtype)
        return jnp.einsum("bhsl,blc->bshc", p, c_all,
                          preferred_element_type=jnp.float32
                          ).astype(q_lat.dtype)

    if s == 1 or h <= _HEAD_BLOCK or h % _HEAD_BLOCK:
        return attend(q_lat, q_rope)

    def blocks(q):     # (B, S, H, W) -> (H / 8, B, S, 8, W)
        return jnp.moveaxis(
            q.reshape(b, s, h // _HEAD_BLOCK, _HEAD_BLOCK, -1), 2, 0)

    out = jax.lax.map(lambda qs: attend(*qs), (blocks(q_lat),
                                               blocks(q_rope)))
    return jnp.moveaxis(out, 0, 2).reshape(b, s, h, -1)


def mla_attention_reference(q_lat, q_rope, c_pages, r_pages, block_tables,
                            lengths, scale, pages_per_seq=None):
    """jnp oracle of :func:`paged_mla_attention`: gather every table
    column back into a contiguous cache and mask by length."""
    if (pages_per_seq is not None
            and pages_per_seq < block_tables.shape[1]):
        block_tables = block_tables[:, :pages_per_seq]
    b = q_lat.shape[0]
    tables = jnp.clip(block_tables, 0, c_pages.shape[0] - 1)
    c_all = c_pages[tables].reshape(b, -1, c_pages.shape[-1])
    r_all = r_pages[tables].reshape(b, -1, r_pages.shape[-1])
    cols = jnp.arange(c_all.shape[1])
    mask = (cols[None, :] < lengths[:, None])[:, None, None, :]
    out = latent_attend(q_lat[:, None], q_rope[:, None], c_all, r_all, mask,
                        scale)[:, 0]
    return jnp.where((lengths > 0)[:, None, None], out, 0).astype(
        q_lat.dtype)
