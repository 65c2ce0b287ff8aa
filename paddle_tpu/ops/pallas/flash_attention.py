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

Masking (round 4, the flash_attn varlen/padding analog): per-sequence
valid lengths and/or segment ids are folded into per-token int32 segment
arrays (padding becomes segment ``-1``); the kernels mask score entries
where the q and k segments differ, fwd + both bwd passes. Fully-masked
(padding) query rows produce finite garbage and their lse is degenerate —
harmless because any loss masks those rows, making their upstream
gradient zero, which zeroes every ds contribution through them.

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

from . import interpret as _interpret
from . import over_mesh as _over_mesh

__all__ = ["flash_attention", "flash_attention_supported", "build_segments"]

BLOCK_Q = 128  # minimum/gating granularity
BLOCK_K = 128
# Measured on v5e at (4, 1536, 12, 128): 256x256 blocks run the fwd+bwd in
# 5.2ms vs 11.8ms at 128x128 (VMEM reuse sweet spot); 512x512 regresses.
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
        interpret=_interpret(),
    )(*operands)
    return out, lse


# ------------------------------------------------------------------ backward

def _bwd_dkdv_kernel(*refs, scale, causal, block_q, seq_q, seq_k, masked):
    if masked:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref) = refs
        qseg_ref = kseg_ref = None
    ki = pl.program_id(2)
    g = pl.program_id(3)  # position within the GQA group (0 for MHA)
    k = k_ref[0, 0, :, :].astype(jnp.float32)  # (bk, d)
    v = v_ref[0, 0, :, :].astype(jnp.float32)
    bk, d = k.shape
    kseg = (kseg_ref[0, 0, pl.ds(ki * bk, bk)] if masked
            else None)  # (bk,)

    # the dk/dv block is revisited across the (fastest) group dim: zero it
    # on the first group member, accumulate in place for the rest
    @pl.when(g == 0)
    def _init():
        dk_ref[0, 0, :, :] = jnp.zeros((bk, d), dk_ref.dtype)
        dv_ref[0, 0, :, :] = jnp.zeros((bk, d), dv_ref.dtype)

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    num_q = seq_q // block_q
    off = seq_k - seq_q
    # causal: q rows with r + off < ki*bk see nothing of this k block
    q_start = jnp.maximum(ki * bk - off, 0) // block_q if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        dlt = delta_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + qi * block_q
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + ki * bk
            s = jnp.where(rows + off >= cols, s, NEG_INF)
        if masked:
            qseg = qseg_ref[0, 0, pl.ds(qi * block_q, block_q)]
            s = jnp.where(qseg[:, None] == kseg[None, :], s, NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk)
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # p^T @ do
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (bq, bk)
        ds = p * (dp - dlt) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # ds^T @ q
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(q_start, num_q, body, (dk0, dv0))
    dk_ref[0, 0, :, :] += dk.astype(dk_ref.dtype)
    dv_ref[0, 0, :, :] += dv.astype(dv_ref.dtype)


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
    # grid: group is the FASTEST dim so the (b, kvh, i) dk/dv block is
    # revisited on consecutive steps (init at g==0, accumulate in VMEM)
    dkdv_in_specs = [
        pl.BlockSpec((1, 1, sq, d),
                     lambda b_, j_, i, g_: (b_, j_ * group + g_, 0, 0)),
        pl.BlockSpec((1, 1, BK, d), lambda b_, j_, i, g_: (b_, j_, i, 0)),
        pl.BlockSpec((1, 1, BK, d), lambda b_, j_, i, g_: (b_, j_, i, 0)),
        pl.BlockSpec((1, 1, sq, d),
                     lambda b_, j_, i, g_: (b_, j_ * group + g_, 0, 0)),
        pl.BlockSpec((1, 1, sq, 1),
                     lambda b_, j_, i, g_: (b_, j_ * group + g_, 0, 0)),
        pl.BlockSpec((1, 1, sq, 1),
                     lambda b_, j_, i, g_: (b_, j_ * group + g_, 0, 0)),
    ]
    dkdv_operands = [q, k, v, do, lse, delta]
    if masked:
        dkdv_in_specs += [
            pl.BlockSpec((1, 1, sq), lambda b_, j_, i, g_: (b_, 0, 0)),
            pl.BlockSpec((1, 1, sk), lambda b_, j_, i, g_: (b_, 0, 0)),
        ]
        dkdv_operands += [q_seg, k_seg]
    dkdv = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, scale=scale, causal=causal,
                          block_q=BQ, seq_q=sq, seq_k=sk, masked=masked),
        grid=(b, kvh, sk // BK, group),
        in_specs=dkdv_in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, BK, d), lambda b_, j_, i, g_: (b_, j_, i, 0)),
            pl.BlockSpec((1, 1, BK, d), lambda b_, j_, i, g_: (b_, j_, i, 0)),
        ],
        out_shape=[
            # GQA (group>1): f32 accumulators so the cross-group revisit
            # adds never round through bf16; MHA keeps the input dtype
            # (no revisits, no extra HBM footprint or cast kernels)
            jax.ShapeDtypeStruct((b, kvh, sk, d),
                                 jnp.float32 if group > 1 else k.dtype),
            jax.ShapeDtypeStruct((b, kvh, sk, d),
                                 jnp.float32 if group > 1 else v.dtype),
        ],
        interpret=_interpret(),
    )(*dkdv_operands)
    dk, dv = dkdv
    if dk.dtype != k.dtype:
        dk = dk.astype(k.dtype)
    if dv.dtype != v.dtype:
        dv = dv.astype(v.dtype)

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
        interpret=_interpret(),
    )(*dq_operands)

    return dq, dk, dv, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_bhsd(q, k, v, q_seg, k_seg, causal, scale):
    out, _ = _fwd(q, k, v, causal, scale, q_seg, k_seg)
    return out


def _flash_fwd_rule(q, k, v, q_seg, k_seg, causal, scale):
    out, lse = _fwd(q, k, v, causal, scale, q_seg, k_seg)
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
    accumulates over the group in VMEM, so KV memory/bandwidth stays at
    the grouped size.

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
