"""Fused elementwise Pallas kernels: RoPE and bias-dropout-residual-LN.

Round out the reference's §2.2 fusion set
(/root/reference/paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu:27 and
fused_bias_dropout_residual_layer_norm): one HBM pass each instead of the
separate add/dropout/normalize round-trips.

* ``fused_rope(q, k, cos, sin)`` — neox-style rotary embedding applied to
  q and k in one kernel; custom_vjp (the adjoint is the same rotation with
  the inverse half-swap), so it runs under jit/grad.
* ``bias_dropout_residual_ln`` — ``layer_norm(residual + dropout(x+bias))``
  in one forward kernel with on-chip PRNG for the dropout mask
  (``pltpu.prng_random_bits``), saving (mask, mean, rstd) for an exact
  XLA backward.

Both interpret off-TPU so CI exercises the same code path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret as _interpret
from . import over_mesh as _over_mesh

__all__ = ["fused_rope", "fused_rope_supported",
           "bias_dropout_residual_ln"]


# ------------------------------------------------------------------ RoPE

def fused_rope_supported(q, cos, position_ids=None, use_neox_rotary_style=True):
    return (position_ids is None
            and use_neox_rotary_style and q is not None and q.ndim == 4
            and q.shape[-1] % 2 == 0)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, inverse):
    x = x_ref[0, 0, :, :].astype(jnp.float32)           # (S, D)
    c = cos_ref[:, :].astype(jnp.float32)
    s = sin_ref[:, :].astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[:, :half], x[:, half:]
    if inverse:  # adjoint rotation: [x2, -x1]
        rot = jnp.concatenate([x2, -x1], axis=-1)
    else:        # neox rotate-half: [-x2, x1]
        rot = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[0, 0, :, :] = (x * c + rot * s).astype(o_ref.dtype)


def _rope_apply(x, cos, sin, inverse):
    # (B, S, H, D) -> (B, H, S, D): block last-two dims must be the full
    # (S, D) planes for the Mosaic lowering (sub-(8,128) tiles only pass
    # when equal to the array dims)
    b, s, h, d = x.shape
    xt = jnp.swapaxes(x, 1, 2)
    kernel = functools.partial(_rope_kernel, inverse=inverse)
    out = pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, s, d), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((s, d), lambda bi, hi: (0, 0)),
            pl.BlockSpec((s, d), lambda bi, hi: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s, d), lambda bi, hi: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(xt.shape, x.dtype),
        interpret=_interpret(),
    )(xt, cos, sin)
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _rope_one(x, cos, sin):
    return _rope_apply(x, cos, sin, inverse=False)


def _rope_one_fwd(x, cos, sin):
    return _rope_apply(x, cos, sin, inverse=False), (cos, sin)


def _rope_one_bwd(res, g):
    cos, sin = res
    return _rope_apply(g, cos, sin, inverse=True), None, None


_rope_one.defvjp(_rope_one_fwd, _rope_one_bwd)


def fused_rope(q, k, cos, sin):
    """Apply neox rotary embedding to q and k (B, S, H, D); cos/sin are
    (S, D) tables cropped to the sequence length."""
    s = q.shape[1]
    cos = cos.reshape(-1, cos.shape[-1])[:s]
    sin = sin.reshape(-1, sin.shape[-1])[:s]
    def rope(x):
        return _over_mesh(_rope_one, (x, cos, sin), ("b.h.", "..", ".."),
                          "b.h.")

    return rope(q), rope(k) if k is not None else None


# ------------------------------------------- bias + dropout + residual + LN

def _bdrln_kernel(x_ref, res_ref, bias_ref, scale_ref, lnb_ref, mask_ref,
                  y_ref, mean_ref, rstd_ref, *, rate, eps, training):
    x = x_ref[:, :].astype(jnp.float32) + bias_ref[0, :].astype(jnp.float32)
    if training and rate > 0.0:
        z = x * mask_ref[:, :] * (1.0 / (1.0 - rate))
    else:
        z = x
    z = z + res_ref[:, :].astype(jnp.float32)
    mean = jnp.mean(z, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(z - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (z - mean) * rstd
    y = xhat * scale_ref[0, :].astype(jnp.float32) \
        + lnb_ref[0, :].astype(jnp.float32)
    y_ref[:, :] = y.astype(y_ref.dtype)
    mean_ref[:, :] = mean
    rstd_ref[:, :] = rstd


def _block_rows(rows):
    for br in (256, 128, 64, 8):
        if rows % br == 0:
            return br
    return rows  # block == array dim is always a legal Mosaic block


def _bdrln_fwd_call(x2, res2, bias, scale, lnb, mask, rate, eps, training):
    rows, h = x2.shape
    br = _block_rows(rows)
    kernel = functools.partial(_bdrln_kernel, rate=rate, eps=eps,
                               training=training)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, h), x2.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(x2, res2, bias, scale, lnb, mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _bdrln(x2, res2, bias, scale, lnb, mask, rate, eps, training):
    y, _, _ = _bdrln_fwd_call(x2, res2, bias, scale, lnb, mask, rate,
                              eps, training)
    return y


def _bdrln_fwd(x2, res2, bias, scale, lnb, mask, rate, eps, training):
    y, mean, rstd = _bdrln_fwd_call(x2, res2, bias, scale, lnb, mask, rate,
                                    eps, training)
    return y, (x2, res2, bias, scale, mean, rstd, mask)


def _bdrln_bwd(rate, eps, training, saved, dy):
    x2, res2, bias, scale, mean, rstd, mask = saved
    keep = (1.0 / (1.0 - rate)) if (training and rate > 0.0) else 1.0
    xf = x2.astype(jnp.float32) + bias.astype(jnp.float32)  # bias (1, H)
    z = xf * mask * keep + res2.astype(jnp.float32)
    xhat = (z - mean) * rstd
    dyf = dy.astype(jnp.float32)
    dyw = dyf * scale.astype(jnp.float32)
    dz = rstd * (dyw - jnp.mean(dyw, axis=-1, keepdims=True)
                 - xhat * jnp.mean(dyw * xhat, axis=-1, keepdims=True))
    dx_pre = dz * mask * keep
    dx = dx_pre.astype(x2.dtype)
    dres = dz.astype(res2.dtype)
    dbias = jnp.sum(dx_pre, axis=0, keepdims=True).astype(bias.dtype)
    dscale = jnp.sum(dyf * xhat, axis=0, keepdims=True).astype(scale.dtype)
    dlnb = jnp.sum(dyf, axis=0, keepdims=True).astype(scale.dtype)
    return dx, dres, dbias, dscale, dlnb, None  # mask is non-differentiable


_bdrln.defvjp(_bdrln_fwd, _bdrln_bwd)


def bias_dropout_residual_ln(x, residual, bias=None, ln_scale=None,
                             ln_bias=None, dropout_rate=0.5, ln_epsilon=1e-5,
                             training=True, rng_key=None):
    """``layer_norm(residual + dropout(x + bias))`` in one fused kernel
    (upscale_in_train dropout). x/residual: (*, H). The dropout mask is
    drawn outside the kernel (the backward needs it in HBM regardless); the
    kernel fuses bias + mask-scale + residual + normalize into one pass."""
    h = x.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, h)
    res2 = residual.reshape(-1, h)
    bias = (jnp.zeros((1, h), x.dtype) if bias is None
            else bias.reshape(1, h))
    scale = (jnp.ones((1, h), jnp.float32) if ln_scale is None
             else ln_scale.reshape(1, h))
    lnb = (jnp.zeros((1, h), jnp.float32) if ln_bias is None
           else ln_bias.reshape(1, h))
    if training and dropout_rate > 0.0:
        if rng_key is None:
            # framework RNG stream — a fixed PRNGKey(0) here would hand
            # every direct caller the identical mask on every call/layer
            from ...core import random as _random

            rng_key = _random.next_key()
        mask = jax.random.bernoulli(
            rng_key, 1.0 - dropout_rate, x2.shape).astype(jnp.float32)
    else:
        mask = jnp.ones(x2.shape, jnp.float32)
    y = _bdrln(x2, res2, bias, scale, lnb, mask, float(dropout_rate),
               float(ln_epsilon), bool(training))
    return y.reshape(*lead, h)
