"""Fused RMSNorm — Pallas TPU kernel, forward + backward.

TPU re-emission of the reference's fused norm kernels
(/root/reference/paddle/phi/kernels/gpu/rms_norm_kernel.cu:1081 and the
fusion set paddle/phi/kernels/fusion/gpu/fused_layernorm*): one pass over
HBM per direction instead of the separate mean-square/normalize/scale
kernels, with f32 accumulation under bf16 activations.

Rows are blocked over a flattened (N, D) view; the backward accumulates
dweight/dbias across row-blocks inside the kernel, relying on the TPU
grid's sequential iteration order (the Pallas-on-TPU idiom for
reductions across the grid). Off-TPU the kernel runs in interpret mode
so CI exercises the same code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret as _interpret

__all__ = ["rms_norm", "rms_norm_supported"]

BLOCK_ROWS = 256


def rms_norm_supported(x, weight):
    if weight is None:
        return False
    if x.ndim < 2:
        return False
    d = x.shape[-1]
    n = 1
    for s in x.shape[:-1]:
        n *= int(s)
    # row-blocked layout wants lane-aligned D and an even split of rows
    return d % 128 == 0 and d <= 16384 and n % 8 == 0


def _rows_block(n, d, elems=1 << 20):
    # cap the block so the row-blocks stay well inside VMEM (``elems``
    # elements, ~4MB of f32, per buffer)
    cap = max(8, elems // max(d, 1))
    b = BLOCK_ROWS
    while b > cap:
        b //= 2
    while n % b:
        b //= 2
    return max(b, 1)


# ------------------------------------------------------------------ forward

def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, r_ref, *, epsilon, has_bias):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(m + epsilon)
    out = x * r * w_ref[...].astype(jnp.float32)
    if has_bias:
        out = out + b_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)
    r_ref[...] = r


def _fwd(x2, w, b, epsilon):
    # every operand rides as 2-D: Mosaic rejects 1-D blocks whose lane
    # tiling disagrees with the XLA layout of the surrounding program
    n, d = x2.shape
    br = _rows_block(n, d)
    has_bias = b is not None
    bias = (b if has_bias else jnp.zeros((d,), w.dtype)).reshape(1, d)
    out, r = pl.pallas_call(
        functools.partial(_fwd_kernel, epsilon=epsilon, has_bias=has_bias),
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2, w.reshape(1, d), bias)
    return out, r


# ----------------------------------------------------------------- backward

def _bwd_kernel(x_ref, w_ref, r_ref, g_ref, dx_ref, dw_ref, db_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    r = r_ref[...]  # (br, 1)
    d = x.shape[-1]
    gw = g * w
    # y = x*r*w: dx = r*(gw - x * r^2 * mean(gw * x))
    inner = jnp.mean(gw * x, axis=-1, keepdims=True)
    dx = r * (gw - x * (r * r) * inner)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    # cross-row-block reductions: TPU grid runs sequentially, so the
    # first block initializes and later blocks accumulate
    dw_blk = jnp.sum(g * x * r, axis=0, keepdims=True)
    db_blk = jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = dw_blk
        db_ref[...] = db_blk

    @pl.when(i > 0)
    def _acc():
        dw_ref[...] += dw_blk
        db_ref[...] += db_blk


def _bwd_call(x2, w, r, g2):
    n, d = x2.shape
    # half the forward's block: x, g and dx blocks plus their f32
    # temporaries are live together, and at d=4096 the forward's 256 rows
    # needed 20 MB of the chip's 16 MB scoped VMEM
    br = _rows_block(n, d, elems=1 << 19)
    dx, dw, db = pl.pallas_call(
        _bwd_kernel,
        grid=(n // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x2.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2, w.reshape(1, d), r, g2)
    return dx, dw[0], db[0]


# ------------------------------------------------------------------ public

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rms_norm(x, weight, bias, epsilon=1e-6, has_bias=False):
    out, _ = _fwd(x.reshape(-1, x.shape[-1]), weight,
                  bias if has_bias else None, epsilon)
    return out.reshape(x.shape)


def _vjp_fwd(x, weight, bias, epsilon, has_bias):
    x2 = x.reshape(-1, x.shape[-1])
    out, r = _fwd(x2, weight, bias if has_bias else None, epsilon)
    return out.reshape(x.shape), (x2, weight, r, x.shape)


def _vjp_bwd(epsilon, has_bias, res, g):
    x2, w, r, shape = res
    g2 = g.reshape(-1, shape[-1])
    dx, dw, db = _bwd_call(x2, w, r, g2)
    return (dx.reshape(shape), dw.astype(w.dtype),
            db.astype(w.dtype) if has_bias else None)


rms_norm.defvjp(_vjp_fwd, _vjp_bwd)
