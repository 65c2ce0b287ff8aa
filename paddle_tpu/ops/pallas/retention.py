"""Power retention over a fixed recurrent state: Pallas TPU kernels.

A power-retention layer (power 2) scores a query against a key by the
SQUARE of their product, ``(q . k)^2 = phi(q) . phi(k)`` with ``phi(z)`` the
d(d+1)/2 monomials ``z_a z_b`` (a <= b), and gates the past by a scalar
``g_t`` in (0, 1) a kv head. A sequence therefore keeps no keys or values a
token: it keeps, a layer and a kv head, one state ``S = sum_j decay_j
phi(k_j) v_j^T`` and one normaliser ``Z = sum_j decay_j phi(k_j)``, whatever
its length, and reads ``y_t = phi(q_t)^T S / phi(q_t)^T Z``.

Layout of the state. The monomials are laid out as ``R = d/2 + 1`` rows of
``d`` lanes, row r holding the pairs at circular distance r: ``phi(z)[r, l] =
z_l z_(l+r mod d)``, a lane rotation and a multiply, with no gather and no
broadcast. Rows r and d - r hold the same products, so ``(q . k)^2 = row 0 +
2 sum_(0<r<d/2) row r + row d/2``: the weight (1, 2, ..., 2, 1) sits on the
KEY side. Row 0 is the squares; row d/2 holds each of its pairs twice at
weight 1. For d = 128 that is 65 x 128 = 8,320 slots for the 8,256 monomials,
every row a full lane tile. ``S`` is ``(slots, kv heads, R, d_v, d)``: row r
of a head is one ``(d_v, d)`` tile, values on sublanes, the row's monomials
on lanes; ``Z`` is ``(slots, kv heads, R, d)``. Both float32. ``phi`` is
expanded a row at a time in VMEM and never lives in HBM.

Two kernels, each updating the state IN PLACE (``input_output_aliases``):

* ``power_retention_decode``: one token a live row. The grid is a work list
  of (live row, kv head) pairs (a dynamic bound, as ``paged_attention``'s):
  a row that is not live is never visited, so its state stays bit for bit.
  ``S <- g S + v phi(k)^T``, ``Z`` alike, and the group's query heads read the
  new state. Bound by bytes: the state of a live row is read and written once.
* ``power_retention_chunk``: a chunk of L tokens of one row and kv head:
  scores squared and gated under a causal mask inside the chunk, the carried
  state read through ``phi(q)``, and the state at the chunk's end. Bound by
  FLOPs. Masked positions (past a row's true length) come in as ``k = 0`` and
  ``log g = 0``: gate 1, addend 0.

``retention_decode_reference`` / ``retention_chunk_reference`` are the jnp
forms of the same functions over the same layout (the oracle, and the path
where the kernels are off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["state_shapes", "phi", "dense_state", "mask_chunk",
           "power_retention_decode",
           "power_retention_chunk", "retention_decode_reference",
           "retention_chunk_reference"]

F32 = jnp.float32
# a head's state block is 4.26 MB at d = 128; in and out, double-buffered
_VMEM_LIMIT = 64 * 1024 * 1024


def state_shapes(kv_heads, head_dim, v_dim=None):
    """Trailing shapes of ``S`` and ``Z`` after the slot dimension."""
    if head_dim % 2:
        raise ValueError(f"the state's layout needs an even head size, got "
                         f"{head_dim}")
    rows = head_dim // 2 + 1
    return ((kv_heads, rows, v_dim or head_dim, head_dim),
            (kv_heads, rows, head_dim))


def _key_weights(d):
    w = np.full((d // 2 + 1, 1), 2.0, np.float32)
    w[0] = w[-1] = 1.0
    return w


def phi(z, key_side=False):
    """``z`` (..., d) -> (..., R, d) in float32: the monomials in the
    state's layout, weighted on the key side."""
    d = z.shape[-1]
    z = z.astype(F32)
    other = (np.arange(d)[None, :] + np.arange(d // 2 + 1)[:, None]) % d
    out = z[..., None, :] * z[..., other]
    return out * _key_weights(d) if key_side else out


def dense_state(s, z):
    """The state out of its layout, for whoever compares it: ``s`` (..., R,
    d_v, d) and ``z`` (..., R, d) -> the symmetric sums over the whole ``d x
    d`` outer product, ``sum_j decay_j k_j k_j^T (x) v_j`` (..., d, d, d_v)
    and ``sum_j decay_j k_j k_j^T`` (..., d, d). Row r, lane l of the layout
    is the pair (l, l + r mod d) at the key side's weight."""
    d = z.shape[-1]
    w = _key_weights(d)[:, 0]                                  # (R,)
    a = np.broadcast_to(np.arange(d)[None, :], (d // 2 + 1, d))
    b = (a + np.arange(d // 2 + 1)[:, None]) % d
    sd = np.zeros(s.shape[:-3] + (d, d, s.shape[-2]), np.float32)
    zd = np.zeros(z.shape[:-2] + (d, d), np.float32)
    pairs = np.moveaxis(np.asarray(s, np.float32), -2, -1) \
        / w[:, None, None]                                     # (.., R, d, d_v)
    zp = np.asarray(z, np.float32) / w[:, None]
    sd[..., a, b, :] = pairs
    sd[..., b, a, :] = pairs
    zd[..., a, b] = zp
    zd[..., b, a] = zp
    return sd, zd


def mask_chunk(k, logg, true_lens):
    """Positions at or past a row's ``true_lens`` out of the update: key 0
    (addend 0), log-gate 0 (gate 1). ``k`` (B, L, KV, d), ``logg`` (B, L,
    KV), ``true_lens`` (B,) or None."""
    if true_lens is None:
        return k, logg
    real = jnp.arange(k.shape[1])[None, :] < true_lens[:, None]
    return (jnp.where(real[:, :, None, None], k, 0).astype(k.dtype),
            jnp.where(real[:, :, None], logg, 0.0))


# ------------------------------------------------------------------ jnp forms

def retention_decode_reference(q, k, v, logg, s, z, rows, live=None):
    """One token a row. ``q`` (B, H, d); ``k`` (scaled) (B, KV, d); ``v``
    (B, KV, d_v); ``logg`` (B, KV) float32; ``s`` / ``z`` the whole state;
    ``rows`` (B,) each row's slot; ``live`` (B,) bool or None. Returns
    ``(y (B, H, d_v), s, z)``; a row that is not live keeps its state."""
    b, h, _ = q.shape
    kv = k.shape[1]
    g = jnp.exp(logg.astype(F32))
    pk = phi(k, key_side=True)                                 # (B, KV, R, d)
    s_new = (g[:, :, None, None, None] * s[rows]
             + v.astype(F32)[:, :, None, :, None] * pk[:, :, :, None, :])
    z_new = g[:, :, None, None] * z[rows] + pk
    pq = phi(q).reshape(b, kv, h // kv, *pk.shape[2:])         # (B,KV,G,R,d)
    num = jnp.einsum("bcgrl,bcrvl->bcgv", pq, s_new,
                     precision=jax.lax.Precision.HIGHEST)
    den = jnp.einsum("bcgrl,bcrl->bcg", pq, z_new,
                     precision=jax.lax.Precision.HIGHEST)
    y = (num / den[..., None]).reshape(b, h, -1).astype(q.dtype)
    if live is not None:
        s_new = jnp.where(live[:, None, None, None, None], s_new, s[rows])
        z_new = jnp.where(live[:, None, None, None], z_new, z[rows])
    return y, s.at[rows].set(s_new), z.at[rows].set(z_new)


def retention_chunk_reference(q, k, v, logg, s, z, rows):
    """A chunk of L tokens a row continuing its slot's state. ``q`` (B, L,
    H, d); ``k`` (scaled, masked) and ``v`` (B, L, KV, .); ``logg`` (B, L,
    KV) float32 (masked). Returns ``(y (B, L, H, d_v), s, z)``."""
    hi = jax.lax.Precision.HIGHEST
    b, n, h, d = q.shape
    kv = k.shape[2]
    s0, z0 = s[rows], z[rows]
    cum = jnp.cumsum(logg.astype(F32), axis=1)                 # (B, L, KV)
    qg = q.astype(F32).reshape(b, n, kv, h // kv, d)
    kf, vf = k.astype(F32), v.astype(F32)
    sc = jnp.einsum("btcgd,bjcd->bcgtj", qg, kf, precision=hi)
    see = jnp.tril(jnp.ones((n, n), bool))
    gap = cum.transpose(0, 2, 1)[:, :, :, None] \
        - cum.transpose(0, 2, 1)[:, :, None, :]                # (B,KV,t,j)
    a = jnp.where(see, jnp.exp(jnp.where(see, gap, 0.0))[:, :, None]
                  * sc * sc, 0.0)
    pq = phi(qg)                                               # (B,L,KV,G,R,d)
    eb = jnp.exp(cum)[:, :, :, None]                           # (B,L,KV,1)
    num = jnp.einsum("bcgtj,bjcv->btcgv", a, vf, precision=hi) \
        + eb[..., None] * jnp.einsum("btcgrl,bcrvl->btcgv", pq, s0,
                                     precision=hi)
    den = jnp.sum(a, -1).transpose(0, 3, 1, 2) \
        + eb * jnp.einsum("btcgrl,bcrl->btcg", pq, z0, precision=hi)
    y = (num / den[..., None]).reshape(b, n, h, -1).astype(q.dtype)
    w = jnp.exp(cum[:, -1:, :] - cum)                          # (B, L, KV)
    pk = phi(kf, key_side=True) * w[..., None, None]           # (B,L,KV,R,d)
    end = jnp.exp(cum[:, -1, :])                               # (B, KV)
    s1 = end[:, :, None, None, None] * s0 + jnp.einsum(
        "bjcrl,bjcv->bcrvl", pk, vf, precision=hi)
    z1 = end[:, :, None, None] * z0 + jnp.sum(pk, 1)
    return y, s.at[rows].set(s1), z.at[rows].set(z1)


# -------------------------------------------------------------------- kernels

def _phi_row(first, z, r, d):
    """Row ``r`` (traced) of the layout for every row of ``z`` (n, d):
    ``first`` is ``z`` itself or ``z`` already scaled a row."""
    return first * pltpu.roll(z, (d - r) % d, 1)


def _key_weight(r, d):
    return jnp.where((r == 0) | (r == d // 2), 1.0, 2.0)


def _decode_kernel(bidx_ref, srow_ref, qk_ref, vt_ref, g_ref, s_ref, z_ref,
                   num_ref, den_ref, so_ref, zo_ref, acc_ref, *, d, grp):
    del bidx_ref, srow_ref
    qk = qk_ref[0, 0].astype(F32)              # (8.., d): G queries, the key
    vcol = vt_ref[0, 0].astype(F32)                            # (d_v, 1)
    g = jnp.exp(g_ref[0, 0])                                   # (1, 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def row(r, den):
        p = _phi_row(qk, qk, r, d)
        pq, pk = p[:grp], p[grp:grp + 1] * _key_weight(r, d)
        s_new = g * s_ref[0, 0, r] + vcol * pk                 # (d_v, d)
        so_ref[0, 0, r] = s_new
        z_new = g * z_ref[0, 0, pl.ds(r, 1), :] + pk           # (1, d)
        zo_ref[0, 0, pl.ds(r, 1), :] = z_new
        for h in range(grp):
            acc_ref[h] += s_new * pq[h:h + 1, :]
        return den + jnp.sum(pq * z_new, axis=1, keepdims=True)

    den_ref[0, 0] = jax.lax.fori_loop(0, d // 2 + 1, row,
                                      jnp.zeros((grp, 1), F32))
    num_ref[0, 0] = jnp.concatenate(
        [jnp.sum(acc_ref[h], axis=1, keepdims=True) for h in range(grp)],
        axis=1)                                                # (d_v, G)


def power_retention_decode(q, k, v, logg, s, z, rows, live=None):
    """:func:`retention_decode_reference` as a kernel over a work list of
    the live rows; the state is updated in place."""
    b, h, d = q.shape
    kv = k.shape[1]
    grp = h // kv
    dv = v.shape[-1]
    nrows = d // 2 + 1
    if live is None:
        live = jnp.ones((b,), bool)
    # live rows first, in slot order; the grid stops after them
    bidx = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_items = jnp.sum(live, dtype=jnp.int32) * kv
    rows = rows.astype(jnp.int32)
    # a head's group of queries and its key in one block of whole sublane
    # tiles: one rotation a row of the layout serves them all
    nqk = -(-(grp + 1) // 8) * 8
    qk = jnp.concatenate(
        [q.reshape(b, kv, grp, d), k[:, :, None, :].astype(q.dtype),
         jnp.zeros((b, kv, nqk - grp - 1, d), q.dtype)], axis=2)

    def per_row(w, bidx, srow):
        return (bidx[w // kv], w % kv, 0, 0)

    def per_state(w, bidx, srow):
        return (srow[bidx[w // kv]], w % kv, 0, 0, 0)

    def per_z(w, bidx, srow):
        return (srow[bidx[w // kv]], w % kv, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_items,),
        in_specs=[
            pl.BlockSpec((1, 1, nqk, d), per_row),
            pl.BlockSpec((1, 1, dv, 1), per_row),
            pl.BlockSpec((1, 1, 1, 1), per_row),
            pl.BlockSpec((1, 1, nrows, dv, d), per_state),
            pl.BlockSpec((1, 1, nrows, d), per_z),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, dv, grp), per_row),
            pl.BlockSpec((1, 1, grp, 1), per_row),
            pl.BlockSpec((1, 1, nrows, dv, d), per_state),
            pl.BlockSpec((1, 1, nrows, d), per_z),
        ],
        scratch_shapes=[pltpu.VMEM((grp, dv, d), F32)],
    )
    num, den, s, z = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, grp=grp),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, kv, dv, grp), F32),
                   jax.ShapeDtypeStruct((b, kv, grp, 1), F32),
                   jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        # operands count the two scalar-prefetch vectors
        input_output_aliases={5: 2, 6: 3},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="power_retention_decode",
        interpret=_interpret(),
    )(bidx, rows, qk, v[:, :, :, None],
      logg.astype(F32)[:, :, None, None], s, z)
    y = jnp.swapaxes(num, 2, 3) / den                          # (B,KV,G,d_v)
    return y.reshape(b, h, dv).astype(q.dtype), s, z


def _chunk_kernel(srow_ref, q_ref, k_ref, v_ref, bq_ref, br_ref, wk_ref,
                  be_ref, s_ref, z_ref, y_ref, so_ref, zo_ref, num_ref,
                  den_ref, *, d, n, cd):
    del srow_ref
    lanes = (((1,), (1,)), ((), ()))
    q = q_ref[0, 0].astype(F32)                                # (G L, d)
    k = k_ref[0, 0].astype(F32)                                # (L, d)
    v = v_ref[0, 0].astype(cd)                                 # (L, d_v)
    bq, br = bq_ref[0, 0], br_ref[0, 0]
    end = jnp.exp(be_ref[0, 0])                                # (1, 1)
    sc = jax.lax.dot_general(q.astype(cd), k.astype(cd), lanes,
                             preferred_element_type=F32)       # (G L, L)
    t = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) % n
    see = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) <= t
    a = jnp.where(see, jnp.exp(jnp.where(see, bq - br, 0.0)) * sc * sc, 0.0)
    num_ref[...] = jnp.dot(a.astype(cd), v, preferred_element_type=F32)
    den_ref[...] = jnp.sum(a, axis=1, keepdims=True)           # (G L, 1)
    qe = q * jnp.exp(bq)             # the carried state's decay at row t
    kw = k * wk_ref[0, 0]            # a key's decay to the chunk's end

    def row(r, _):
        pq = _phi_row(qe, q, r, d)                             # (G L, d)
        pk = _phi_row(kw, k, r, d) * _key_weight(r, d)         # (L, d)
        s_r = s_ref[0, 0, r]                                   # (d_v, d)
        z_r = z_ref[0, 0, pl.ds(r, 1), :]                      # (1, d)
        num_ref[...] += jax.lax.dot_general(
            pq.astype(cd), s_r.astype(cd), lanes, preferred_element_type=F32)
        den_ref[...] += jnp.sum(pq * z_r, axis=1, keepdims=True)
        # v^T phi(k): contract the chunk's tokens, no transpose
        so_ref[0, 0, r] = end * s_r + jax.lax.dot_general(
            v, pk.astype(cd), (((0,), (0,)), ((), ())),
            preferred_element_type=F32)
        zo_ref[0, 0, pl.ds(r, 1), :] = end * z_r + jnp.sum(
            pk, axis=0, keepdims=True)
        return 0

    jax.lax.fori_loop(0, d // 2 + 1, row, 0)
    y_ref[0, 0] = (num_ref[...] / den_ref[...]).astype(y_ref.dtype)


def power_retention_chunk(q, k, v, logg, s, z, rows):
    """:func:`retention_chunk_reference` as a kernel, a grid step a (row,
    kv head); the state is updated in place. Rows that share a slot (the
    scratch slot of an admission group's padding) overwrite one another
    there, which is what the scratch slot is for."""
    b, n, h, d = q.shape
    kv = k.shape[2]
    grp = h // kv
    dv = v.shape[-1]
    nrows = d // 2 + 1
    cum = jnp.cumsum(logg.astype(F32), axis=1).transpose(0, 2, 1)  # (B,KV,L)
    bq = jnp.tile(cum, (1, 1, grp))[..., None]                 # (B,KV,G L,1)
    wk = jnp.exp(cum[:, :, -1:] - cum)[..., None]              # (B,KV,L,1)
    qh = q.reshape(b, n, kv, grp, d).transpose(0, 2, 3, 1, 4).reshape(
        b, kv, grp * n, d)

    def per_row(i, c, srow):
        return (i, c, 0, 0)

    def per_state(i, c, srow):
        return (srow[i], c, 0, 0, 0)

    def per_z(i, c, srow):
        return (srow[i], c, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv),
        in_specs=[
            pl.BlockSpec((1, 1, grp * n, d), per_row),
            pl.BlockSpec((1, 1, n, d), per_row),
            pl.BlockSpec((1, 1, n, dv), per_row),
            pl.BlockSpec((1, 1, grp * n, 1), per_row),
            pl.BlockSpec((1, 1, 1, n), per_row),
            pl.BlockSpec((1, 1, n, 1), per_row),
            pl.BlockSpec((1, 1, 1, 1), per_row),
            pl.BlockSpec((1, 1, nrows, dv, d), per_state),
            pl.BlockSpec((1, 1, nrows, d), per_z),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, grp * n, dv), per_row),
            pl.BlockSpec((1, 1, nrows, dv, d), per_state),
            pl.BlockSpec((1, 1, nrows, d), per_z),
        ],
        scratch_shapes=[pltpu.VMEM((grp * n, dv), F32),
                        pltpu.VMEM((grp * n, 1), F32)],
    )
    y, s, z = pl.pallas_call(
        functools.partial(_chunk_kernel, d=d, n=n, cd=q.dtype),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, kv, grp * n, dv), q.dtype),
                   jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="power_retention_chunk",
        interpret=_interpret(),
    )(rows.astype(jnp.int32), qh, k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3), bq, cum[:, :, None, :], wk,
      cum[:, :, -1:, None], s, z)
    y = y.reshape(b, kv, grp, n, dv).transpose(0, 3, 1, 2, 4)
    return y.reshape(b, n, h, dv), s, z
