"""Grouped matrix product for sparse experts — Pallas TPU kernel.

``moe_gmm(lhs, rhs, group_sizes)``: the rows of ``lhs`` (M, K) are sorted
by expert, ``group_sizes`` (E,) says how many consecutive rows each expert
owns, and row i is multiplied by ITS expert's matrix ``rhs[e]`` (K, N).
Rows past ``sum(group_sizes)`` belong to nobody and come back undefined:
the caller masks them.

The grid is a WORK LIST, as in ``decode_attention``: one item for every
(expert, row tile) pair in which the expert has rows, built from
``group_sizes`` with a few XLA ops and handed over as scalar prefetch
(``pltpu.PrefetchScalarGridSpec``), its length a dynamic grid dimension.
An expert without rows has no item, so its weights are never read: a
decode step of ~150 assignments over ~116 of 256 experts reads 116
matrices, each once (with 128-row tiles the 256 sorted rows of 32 slots x
top-8 are two tiles, and only an expert that straddles the boundary is
read twice). The whole K is one block (K <= 2048 here: a (K, 512) bf16
block is 2 MiB), so an item is one MXU product and one masked store of the
expert's own rows into the tile's output block, which stays resident in
VMEM across the consecutive items of a tile.

Why not ``jax.experimental.pallas.ops.tpu.megablox.gmm``, which this JAX
ships and whose metadata scheme this follows: it takes no ``name=`` (the
benchmark finds a kernel by its name in the device trace) and is a jitted
function of its own with a static tiling argument. It is no slower: given
this kernel's tiling (128, whole K, 512) it reads the same on the chip at
the decode shape (1,016 + 592 us against 1,001 + 575 for 115 experts hit,
bytes' floor 1,325), and 8 times slower at its default of 128 cubed
(``PERF.md`` §6, PR 27). So this is the same scheme in a third of the
code, without the K loop and its accumulator that no shape here needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["moe_gmm", "gmm_reference", "row_tile", "col_tile"]

_TN = 512          # columns of rhs a grid step reads: (K, 512) bf16 = 2 MiB


def col_tile(n: int) -> int:
    """Columns of rhs a grid step reads: the largest multiple of 128 up to
    512 that divides ``n`` (512 for 1,536 and 2,048; 384 for 1,920 and
    2,688), else all of them."""
    for tn in range(_TN, 0, -128):
        if n % tn == 0:
            return tn
    return n


def row_tile(m: int) -> int:
    """Rows a grid step multiplies: 128 (one MXU pass holds them whatever
    their number, so a smaller tile saves nothing) or, under that, ``m``
    rounded up to the 16 rows a packed bf16 tile holds; prefill's
    thousands of rows take 256. The caller pads M to a multiple."""
    if m > 2048:
        return 256
    return min(128, -(-m // 16) * 16)


def _gmm_work_list(group_sizes, m, tm):
    """(expert, row tile) pairs in which the expert has rows, expert-major
    (and so tile-major too: rows are sorted by expert), as int32 vectors
    of the static ceiling ``m / tm + E``; the row offsets of the experts
    (E + 1,); and how many pairs are real."""
    e = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts // tm
    ntiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    cum = jnp.cumsum(ntiles)
    item = jnp.arange(m // tm + e, dtype=jnp.int32)
    done = item[:, None] >= cum[None, :]      # experts wholly before an item
    groups = jnp.minimum(jnp.sum(done, axis=1, dtype=jnp.int32), e - 1)
    before = jnp.sum(jnp.where(done, ntiles[None, :], 0), axis=1)
    tiles = jnp.clip(first[groups] + item - before, 0, m // tm - 1)
    return groups, tiles.astype(jnp.int32), offsets, cum[-1]


def _gmm_kernel(groups_ref, tiles_ref, offs_ref, lhs_ref, rhs_ref, out_ref,
                *, tm):
    i = pl.program_id(1)
    g = groups_ref[i]
    acc = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (tm, tn)
    rows = (jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            + tiles_ref[i] * tm)
    mine = (rows >= offs_ref[g]) & (rows < offs_ref[g + 1])
    # the block holds the rows other experts of this tile already wrote
    # (and, before the first, whatever VMEM held: every row under
    # sum(group_sizes) is written by its own expert's item)
    out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def moe_gmm(lhs, rhs, group_sizes):
    """``out[i] = lhs[i] @ rhs[e(i)]`` for rows sorted by expert.

    lhs (M, K), M a multiple of ``row_tile(M)``; rhs (E, K, N) in lhs's
    type; group_sizes (E,) int32, sum <= M. Returns (M, N) in lhs's type;
    rows from ``sum(group_sizes)`` on are undefined."""
    m, k = lhs.shape
    e, _, n = rhs.shape
    tm = row_tile(m)
    if m % tm:
        raise ValueError(f"moe_gmm: {m} rows are no multiple of the row "
                         f"tile {tm}; pad them")
    tn = col_tile(n)
    group_sizes = group_sizes.astype(jnp.int32)
    groups, tiles, offsets, total = _gmm_work_list(group_sizes, m, tm)

    def lhs_map(j, i, groups, tiles, offs):
        return (tiles[i], 0)

    def rhs_map(j, i, groups, tiles, offs):
        return (groups[i], 0, j)

    def out_map(j, i, groups, tiles, offs):
        return (tiles[i], j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, total),
        in_specs=[
            pl.BlockSpec((tm, k), lhs_map),
            pl.BlockSpec((None, k, tn), rhs_map),
        ],
        out_specs=pl.BlockSpec((tm, tn), out_map),
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="moe_gmm",
        interpret=_interpret(),
    )(groups, tiles, offsets, lhs, rhs.astype(lhs.dtype))


def gmm_reference(lhs, rhs, group_sizes):
    """jnp oracle of :func:`moe_gmm`: a loop over the experts, each
    multiplying every row and keeping its own; rows past the groups' sum
    come back zero."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    rows = jnp.arange(m)[:, None]

    def one(e, out):
        y = jnp.dot(lhs, rhs[e], preferred_element_type=jnp.float32)
        return jnp.where((rows >= starts[e]) & (rows < ends[e]), y, out)

    out = jax.lax.fori_loop(
        0, rhs.shape[0], one,
        jnp.zeros((m, rhs.shape[2]), jnp.float32))
    return out.astype(lhs.dtype)
