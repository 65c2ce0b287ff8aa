"""Mamba-2's selective state-space step (SSD) over a fixed recurrent state:
Pallas TPU kernels.

A Mamba-2 layer keeps, a sequence and a head ``h`` (of ``H`` heads of ``P``
channels; head ``h`` reads group ``g = h // (H / G)`` of ``G`` groups), one
state ``S_h`` of ``P x N`` float32 whatever the sequence's length:

    S_h,t = a_h,t S_h,t-1 + dt_h,t x_h,t B_g,t^T       y_h,t = S_h,t C_g,t

with ``a = exp(-dt exp(A_log))`` a scalar a head a token, ``x`` (P,), ``B`` and
``C`` (N,). The state array is ``(slots, H, P, N)`` float32, ``N`` on lanes.
The skip ``D x``, the gate and the norm are the layer's, outside.

Two kernels, each updating the state IN PLACE (``input_output_aliases``):

* ``ssd_decode``: one token a live row. The grid is a work list of (live row,
  block of heads) pairs (a dynamic bound, as ``paged_attention``'s): a row
  that is not live is never visited, so its state stays bit for bit. The
  outer product ``x B^T`` is ``diag(x) @ rows(B)`` on the MXU (a product a
  term, so one bfloat16 pass is exact for bfloat16 ``x`` and ``B``), and ``S
  C`` a transposed product of the group's stacked heads. Bound by bytes: the
  state of a live row is read and written once.
* ``ssd_chunk``: a chunk of L tokens of one row and one group of heads, the
  chunked form: ``C B^T`` once a group, a head's decay mask ``exp(cum_t -
  cum_j)`` (j <= t) over it times ``dt_j`` against ``x``, the carried state read
  through ``C`` at ``exp(cum_t)``, and the state at the chunk's end. Bound by
  FLOPs. Masked positions (past a row's true length) come in as ``dt = 0``:
  decay 1, addend 0.

``ssd_decode_reference`` / ``ssd_chunk_reference`` are the jnp forms of the
same functions (the oracle, and the path where the kernels are off).
``causal_conv`` is the depthwise convolution ahead of the step with its
carried inputs, in jnp on both paths (3 inputs a channel: noise beside the
state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = ["ssd_decode", "ssd_chunk", "ssd_decode_reference",
           "ssd_chunk_reference", "causal_conv", "mask_steps"]

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
# a group's block of the state is 8 heads x 64 x 128 float32 = 256 KB
_VMEM_LIMIT = 64 * 1024 * 1024


def mask_steps(dt, true_lens):
    """Positions at or past a row's ``true_lens`` out of the update: ``dt``
    0 (decay 1, addend 0). ``dt`` (B, L, H); ``true_lens`` (B,) or None."""
    if true_lens is None:
        return dt
    real = jnp.arange(dt.shape[1])[None, :] < true_lens[:, None]
    return jnp.where(real[:, :, None], dt, 0.0)


def causal_conv(xbc, weight, bias, carried, true_lens=None):
    """Depthwise causal convolution of width K over ``xbc`` (B, L, C)
    behind the row's ``carried`` last K - 1 inputs (B, K - 1, C): ``out_t =
    sum_k w_k in_(t + k - K + 1) + b``; ``weight`` (K, C), ``bias`` (C,).
    Returns ``(out (B, L, C) float32, carried')``: the last K - 1 REAL
    inputs, those before ``true_lens`` (B,) where it is given (padding is
    never carried), in ``carried``'s type."""
    k = weight.shape[0]
    n = xbc.shape[1]
    window = jnp.concatenate([carried.astype(F32), xbc.astype(F32)], axis=1)
    out = bias.astype(F32) + sum(
        weight[i].astype(F32) * window[:, i:i + n] for i in range(k))
    if true_lens is None:
        keep = window[:, n:]
    else:
        at = true_lens.astype(jnp.int32)[:, None] + jnp.arange(k - 1)[None, :]
        keep = jnp.take_along_axis(window, at[:, :, None], axis=1)
    return out, keep.astype(carried.dtype)


# ------------------------------------------------------------------ jnp forms

def _grouped(a, heads):
    """(..., G, N) -> (..., H, N): head h reads group h // (H / G)."""
    return jnp.repeat(a, heads // a.shape[-2], axis=-2)


def ssd_decode_reference(x, dt, a, bm, cm, state, rows, live=None):
    """One token a row. ``x`` (B, H, P); ``dt`` and the decay ``a`` (B, H)
    float32; ``bm`` / ``cm`` (B, G, N); ``state`` the whole (slots, H, P, N)
    float32 array; ``rows`` (B,) each row's slot; ``live`` (B,) bool or None.
    Returns ``(y (B, H, P) float32, state)``; a row that is not live keeps
    its state and reads 0 (whatever it read would be written on into its
    slot's pages by the layers behind, and the kernel never visits it)."""
    h = x.shape[1]
    old = state[rows]
    bh, ch = _grouped(bm.astype(F32), h), _grouped(cm.astype(F32), h)
    new = (a[:, :, None, None] * old
           + (dt[:, :, None] * x.astype(F32))[..., None] * bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", new, ch, precision=HI)
    if live is not None:
        new = jnp.where(live[:, None, None, None], new, old)
        y = jnp.where(live[:, None, None], y, 0.0)
    return y, state.at[rows].set(new)


def ssd_chunk_reference(x, dt, da, bm, cm, state, rows):
    """A chunk of L tokens a row continuing its slot's state. ``x`` (B, L,
    H, P); ``dt`` (masked) and the log-decay ``da = -dt exp(A_log)`` (B, L,
    H) float32; ``bm`` / ``cm`` (B, L, G, N). Returns ``(y (B, L, H, P)
    float32, state)``."""
    h = x.shape[2]
    s0 = state[rows]                                           # (B, H, P, N)
    cum = jnp.cumsum(da, axis=1)                               # (B, L, H)
    xf = x.astype(F32)
    bh, ch = _grouped(bm.astype(F32), h), _grouped(cm.astype(F32), h)
    n = x.shape[1]
    see = jnp.tril(jnp.ones((n, n), bool))
    gap = cum[:, :, None, :] - cum[:, None, :, :]              # (B, t, j, H)
    decay = jnp.where(see[None, :, :, None],
                      jnp.exp(jnp.where(see[None, :, :, None], gap, 0.0)),
                      0.0)
    sc = jnp.einsum("bthn,bjhn->btjh", ch, bh, precision=HI)
    w = sc * decay * dt[:, None, :, :]
    y = jnp.einsum("btjh,bjhp->bthp", w, xf, precision=HI) \
        + jnp.exp(cum)[..., None] * jnp.einsum(
            "bthn,bhpn->bthp", ch, s0, precision=HI)
    tail = jnp.exp(cum[:, -1:, :] - cum) * dt                  # (B, L, H)
    s1 = jnp.exp(cum[:, -1, :])[:, :, None, None] * s0 + jnp.einsum(
        "bjhp,bjhn->bhpn", xf * tail[..., None], bh, precision=HI)
    return y, state.at[rows].set(s1)


# -------------------------------------------------------------------- kernels

def _decode_kernel(bidx_ref, srow_ref, x_ref, b_ref, c_ref, a_ref, dt_ref,
                   s_ref, y_ref, so_ref, *, hb, per, p, prec):
    del bidx_ref, srow_ref
    n = s_ref.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (p, p), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (p, p), 1))
    for gl in range(hb // per):                    # the block's groups
        rows_b = jnp.broadcast_to(b_ref[0, gl:gl + 1, :], (p, n))
        for hl in range(gl * per, (gl + 1) * per):
            xr = x_ref[0, 0:1, hl * p:(hl + 1) * p]             # (1, P)
            # selected in float32 (a 32-bit mask), multiplied in x's type
            diag = jnp.where(eye, jnp.broadcast_to(xr.astype(F32), (p, p)),
                             0.0).astype(xr.dtype)
            outer = jnp.dot(diag, rows_b, preferred_element_type=F32,
                            precision=prec)                     # (P, N)
            so_ref[0, hl] = (a_ref[0, hl:hl + 1, :] * s_ref[0, hl]
                             + dt_ref[0, hl:hl + 1, :] * outer)
        stacked = so_ref[0, gl * per:(gl + 1) * per].reshape(per * p, n)
        c8 = jnp.broadcast_to(c_ref[0, gl:gl + 1, :].astype(F32), (8, n))
        y = jax.lax.dot_general(c8, stacked, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32, precision=HI)
        y_ref[0, gl:gl + 1, :] = y[0:1, :]                      # (1, per P)


def ssd_decode(x, dt, a, bm, cm, state, rows, live=None, heads_block=32):
    """:func:`ssd_decode_reference` as a kernel over a work list of the
    live rows; the state is updated in place."""
    b, h, p = x.shape
    g, n = bm.shape[1], bm.shape[2]
    per = h // g                                   # heads a group
    hb = max(per, min(heads_block, h) // per * per)
    while h % hb:
        hb -= per
    nblk = h // hb
    if live is None:
        live = jnp.ones((b,), bool)
    # live rows first, in slot order; the grid stops after them
    bidx = jnp.argsort(~live, stable=True).astype(jnp.int32)
    n_items = jnp.sum(live, dtype=jnp.int32) * nblk
    rows = rows.astype(jnp.int32)
    # a head's two scalars along its state's lanes: a row of N a head
    a_l = jnp.broadcast_to(a.astype(F32)[:, :, None], (b, h, n))
    dt_l = jnp.broadcast_to(dt.astype(F32)[:, :, None], (b, h, n))
    prec = HI if x.dtype == F32 else None

    # x, B, C and y go a (row, block) a leading index, so that a block's
    # trailing dimensions are the array's own
    def per_item(w, bidx, srow):
        return (bidx[w // nblk] * nblk + w % nblk, 0, 0)

    def per_row(w, bidx, srow):
        return (bidx[w // nblk], w % nblk, 0)

    def per_state(w, bidx, srow):
        return (srow[bidx[w // nblk]], w % nblk, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_items,),
        in_specs=[
            pl.BlockSpec((1, 1, hb * p), per_item),            # x
            pl.BlockSpec((1, hb // per, n), per_item),         # B
            pl.BlockSpec((1, hb // per, n), per_item),         # C
            pl.BlockSpec((1, hb, n), per_row),                 # decay
            pl.BlockSpec((1, hb, n), per_row),                 # dt
            pl.BlockSpec((1, hb, p, n), per_state),
        ],
        out_specs=[
            pl.BlockSpec((1, hb // per, per * p), per_item),
            pl.BlockSpec((1, hb, p, n), per_state),
        ],
    )
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb, per=per, p=p, prec=prec),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b * nblk, hb // per, per * p), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the two scalar-prefetch vectors
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="ssd_decode",
        interpret=_interpret(),
    )(bidx, rows, x.reshape(b * nblk, 1, hb * p),
      bm.astype(x.dtype).reshape(b * nblk, hb // per, n),
      cm.astype(x.dtype).reshape(b * nblk, hb // per, n), a_l, dt_l, state)
    # a row the grid never visited holds whatever the buffer held
    return jnp.where(live[:, None, None], y.reshape(b, h, p), 0.0), state


def _chunk_kernel(srow_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref, dtt_ref,
                  cumt_ref, s_ref, y_ref, so_ref, *, per, p, cd):
    del srow_ref
    gi = pl.program_id(1)
    n_tok = x_ref.shape[1]
    lanes = (((1,), (1,)), ((), ()))
    bg = b_ref[0].astype(cd)                                   # (L, N)
    cg = c_ref[0].astype(cd)
    sc = jax.lax.dot_general(cg, bg, lanes,
                             preferred_element_type=F32)       # (L, L)
    t = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    see = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) <= t
    # this group's heads' columns of the (L, H) and rows of the (H, L) forms
    cum_all, dt_all = cum_ref[0], dt_ref[0]                    # (L, H)
    cumt_all, dtt_all = cumt_ref[0], dtt_ref[0]                # (H, L)
    col = jax.lax.broadcasted_iota(jnp.int32, cum_all.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, cumt_all.shape, 0)
    for hl in range(per):
        hh = gi * per + hl
        cum_c = jnp.sum(jnp.where(col == hh, cum_all, 0.0), axis=1,
                        keepdims=True)                         # (L, 1)
        dt_c = jnp.sum(jnp.where(col == hh, dt_all, 0.0), axis=1,
                       keepdims=True)
        cum_r = jnp.sum(jnp.where(row == hh, cumt_all, 0.0), axis=0,
                        keepdims=True)                         # (1, L)
        dt_r = jnp.sum(jnp.where(row == hh, dtt_all, 0.0), axis=0,
                       keepdims=True)
        end = cum_r[:, n_tok - 1:n_tok]                        # (1, 1)
        w = jnp.where(see, jnp.exp(jnp.where(see, cum_c - cum_r, 0.0))
                      * sc * dt_r, 0.0)
        xh = x_ref[0, :, hl * p:(hl + 1) * p]                  # (L, P)
        s_h = s_ref[0, hl]                                     # (P, N)
        y = jnp.dot(w.astype(cd), xh.astype(cd),
                    preferred_element_type=F32)
        y += jnp.exp(cum_c) * jax.lax.dot_general(
            cg, s_h.astype(cd), lanes, preferred_element_type=F32)
        y_ref[0, :, hl * p:(hl + 1) * p] = y
        # x^T B over the chunk's tokens, each decayed to the chunk's end
        xw = (xh.astype(F32) * (jnp.exp(end - cum_c) * dt_c)).astype(cd)
        so_ref[0, hl] = jnp.exp(end) * s_h + jax.lax.dot_general(
            xw, bg, (((0,), (0,)), ((), ())), preferred_element_type=F32)


def ssd_chunk(x, dt, da, bm, cm, state, rows):
    """:func:`ssd_chunk_reference` as a kernel, a grid step a (row, group
    of heads); the state is updated in place. Rows that share a slot (the
    scratch slot of an admission group's padding) overwrite one another
    there, which is what the scratch slot is for."""
    b, n_tok, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    per = h // g
    cum = jnp.cumsum(da.astype(F32), axis=1)                   # (B, L, H)
    dt = dt.astype(F32)

    def per_row(i, c, srow):
        return (i, 0, 0)

    def per_group(i, c, srow):
        return (i, 0, c)

    def per_state(i, c, srow):
        return (srow[i], c, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, g),
        in_specs=[
            pl.BlockSpec((1, n_tok, per * p), per_group),      # x
            pl.BlockSpec((1, n_tok, n), per_group),            # B
            pl.BlockSpec((1, n_tok, n), per_group),            # C
            pl.BlockSpec((1, n_tok, h), per_row),              # dt
            pl.BlockSpec((1, n_tok, h), per_row),              # cum
            pl.BlockSpec((1, h, n_tok), per_row),              # dt^T
            pl.BlockSpec((1, h, n_tok), per_row),              # cum^T
            pl.BlockSpec((1, per, p, n), per_state),
        ],
        out_specs=[
            pl.BlockSpec((1, n_tok, per * p), per_group),
            pl.BlockSpec((1, per, p, n), per_state),
        ],
    )
    y, state = pl.pallas_call(
        functools.partial(_chunk_kernel, per=per, p=p, cd=x.dtype),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, n_tok, h * p), F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        name="ssd_chunk",
        interpret=_interpret(),
    )(rows.astype(jnp.int32), x.reshape(b, n_tok, h * p),
      bm.reshape(b, n_tok, g * n), cm.reshape(b, n_tok, g * n), dt, cum,
      dt.transpose(0, 2, 1), cum.transpose(0, 2, 1), state)
    return y.reshape(b, n_tok, h, p), state
