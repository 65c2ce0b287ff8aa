"""Decode megakernel — ONE fused Pallas call per decoder layer (ISSUE 20).

A serving decode step spends ~325us across many small launches (rope,
page-table gather, paged attention, norms, residual adds — see
``OPBENCH_BASELINE.json``); decode is memory-bandwidth-bound, so every
extra launch re-reads the activations HBM<->VMEM for free work. This
kernel collapses the whole attention half of a ``LlamaDecoderLayer``
decode step (s=1, paged cache, per-slot depths) into a single
``pallas_call``:

    rms_norm(ln1) -> q/k/v projections -> rope (neox, per-slot position)
    -> paged-KV append (in-VMEM row substitution + aliased page write)
    -> paged attention (the ``decode_attention._decode_kernel`` online
    softmax, extended with the appended row) -> o_proj -> residual add
    -> rms_norm(ln2)

The MLP half stays in XLA (its matmuls dwarf launch overhead) where the
jit elementwise-chain fusion pass (``paddle_tpu/jit/fusion.py``) groups
its pointwise remainder.

Grid and memory layout extend ``decode_attention``: grid
``(B, pages_per_seq)``, block tables + PRE-append lengths ride as
scalar-prefetch operands, online-softmax state in VMEM scratch across
the page dimension. The projection weights are whole VMEM blocks —
``megakernel_supported`` enforces a VMEM footprint budget, so large
models decline to the unfused path (that is what the capability probe
is FOR; serving-class small models fit comfortably).

Append semantics replicate ``PagedKVCache.update`` exactly: the kernel
receives PRE-append lengths; the new token's k/v row is substituted
in-VMEM at ``(lengths[b] // page_size, lengths[b] % page_size)`` (no
HBM read-after-write hazard) and attention runs over ``lengths[b]+1``
positions. The k/v page pools are input/output-aliased; page-block
writes outside the append page are redirected to the engine's
sacrificial dump page (PR 14's idiom) so Mosaic's output-revisiting
collapses them, or — when no dump page exists — written back in place
unchanged.

Fallback semantics: on CPU the serving engine keeps the exact unfused
composition (bit-identical streams by construction); the Pallas kernel
itself runs under ``interpret=True`` in dedicated tests and in the
forced mode (``FLAGS_decode_megakernel=2``).
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

__all__ = [
    "fused_decode_layer", "reference_decode_layer",
    "megakernel_supported", "megakernel_layer_supported",
    "megakernel_model_supported",
    "megakernel_scope", "megakernel_enabled", "megakernel_kernel_active",
    "megakernel_mode", "MEGAKERNEL_VMEM_BUDGET",
]

NEG_INF = -1e30
_LANES = 128  # TPU vreg lane width

# whole projection weight blocks must fit VMEM (~16MB/core) next to the
# page blocks and scratch; models past this budget decline to unfused
MEGAKERNEL_VMEM_BUDGET = 12 * 2 ** 20

# trace-time override stack: the serving engine builds its unfused
# segment program under megakernel_scope(False) and the fused one under
# megakernel_scope(True), so one flag flip can never retrace the other
_SCOPE = []


def megakernel_mode():
    """FLAGS_decode_megakernel: 0 = off, 1 = auto (Pallas kernel on TPU,
    exact unfused composition on CPU), 2 = force the Pallas kernel even
    off-TPU (interpret mode — tests/benches)."""
    from ...core.flags import flag
    try:
        return int(flag("FLAGS_decode_megakernel"))
    except Exception:
        return 1


@contextlib.contextmanager
def megakernel_scope(on):
    """Pin megakernel dispatch for the enclosed trace (overrides the
    flag): serving program builds use this so fused/unfused segment
    programs are each deterministic regardless of flag state."""
    _SCOPE.append(bool(on))
    try:
        yield
    finally:
        _SCOPE.pop()


def megakernel_enabled():
    if _SCOPE:
        return _SCOPE[-1]
    return megakernel_mode() > 0


def megakernel_kernel_active():
    """True when an eligible decode step should run the Pallas kernel
    right now (vs. the exact unfused composition)."""
    if not megakernel_enabled():
        return False
    return not _interpret() or megakernel_mode() >= 2


def _weight_bytes(*arrays):
    return sum(a.size * a.dtype.itemsize for a in arrays)


def megakernel_layer_supported(layer):
    """Structural probe over one decoder layer: standard LLaMA layout
    (bias-free projections, RMSNorm without bias, neox rope tables,
    GQA-divisible heads), a head_dim Mosaic can lay out, and projection
    weights within the VMEM budget. Mirrors ``paged_attention_supported``
    in spirit: callers branch, the kernel itself assumes eligibility."""
    try:
        attn = layer.self_attn
        cfg = attn.config
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        if h % kv or d % 2:
            return False
        if not _interpret() and d % _LANES:
            # compiled for the chip, the per-head (1, d) rows and the
            # (page, d) page planes must fill whole 128-lane vregs:
            # Mosaic refuses head_dim 64 ("infer-vector-layout:
            # unsupported shape cast"). The interpreter has no lanes.
            return False
        for lin in (attn.q_proj, attn.k_proj, attn.v_proj, attn.o_proj):
            if getattr(lin, "bias", None) is not None:
                return False
        for ln in (layer.input_layernorm, layer.post_attention_layernorm):
            if getattr(ln, "weight", None) is None:
                return False
            if getattr(ln, "bias", None) is not None:
                return False
        if not hasattr(attn, "rope_cos") or not hasattr(attn, "rope_sin"):
            return False
        wb = _weight_bytes(attn.q_proj.weight._value,
                           attn.k_proj.weight._value,
                           attn.v_proj.weight._value,
                           attn.o_proj.weight._value)
        if wb > MEGAKERNEL_VMEM_BUDGET:
            return False
    except AttributeError:
        return False
    return True


def megakernel_model_supported(model):
    """True when the model carries at least one decoder layer and EVERY
    decoder layer passes ``megakernel_layer_supported`` (the engine-level
    capability probe behind FLAGS_decode_megakernel)."""
    if hasattr(model, "kv_page_shapes"):
        # the kernel fuses a dense block's q/k/v projections over (k, v)
        # pages; a model that keeps another cache (latent attention)
        # stays on the unfused segment program
        return False
    layers = [l for l in model.sublayers()
              if hasattr(l, "self_attn") and hasattr(l, "mlp")
              and hasattr(l, "input_layernorm")
              and hasattr(l, "post_attention_layernorm")]
    return bool(layers) and all(megakernel_layer_supported(l)
                                for l in layers)


def megakernel_supported(layer, cache):
    """Full eligibility for ONE fused decode step: supported layer
    structure + a paged cache with per-slot depths."""
    if not megakernel_layer_supported(layer):
        return False
    k_pages = getattr(cache, "k_pages", None)
    if k_pages is None or k_pages.ndim != 4:
        return False
    length = getattr(cache, "length", None)
    return getattr(length, "ndim", None) == 1


# --------------------------------------------------------------- kernel


def _megakernel(tables_ref, lens_ref, x_ref, ln1_ref, ln2_ref,
                wq_ref, wk_ref, wv_ref, wo_ref, cos_ref, sin_ref,
                k_ref, v_ref,
                hmid_ref, y2_ref, ko_ref, vo_ref,
                q_s, k_s, v_s, m_s, l_s, acc_s,
                *, scale, page_size, pages_per_seq, kvh, heads,
                eps1, eps2, writeback):
    b = pl.program_id(0)
    p = pl.program_id(1)
    h = heads
    d = q_s.shape[1]
    group = h // kvh
    length = lens_ref[b]                 # PRE-append context length
    p_app = length // page_size
    off = length % page_size

    @pl.when(p == 0)
    def _project():
        # input rms_norm — the exact jnp-fallback math of F.rms_norm
        # (traced programs always take that path), so fused == unfused
        xr = x_ref[0]                                    # (1, hidden)
        dt = xr.dtype
        xf = xr.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        xn = (xf * jax.lax.rsqrt(var + eps1)).astype(dt)
        xn = xn * ln1_ref[...]
        xnf = xn.astype(jnp.float32)
        # this slot's rope row, gathered by the caller at the decode
        # position and already in the model dtype
        c = cos_ref[0].astype(jnp.float32)               # (1, d)
        s = sin_ref[0].astype(jnp.float32)
        half = d // 2

        def proj(w_ref, i):
            # one head's (1, hidden) x (hidden, d) dot, rounded to the
            # model dtype where the unfused Linear rounds its output
            return jnp.dot(xnf, w_ref[:, i * d:(i + 1) * d]
                           .astype(jnp.float32),
                           preferred_element_type=jnp.float32
                           ).astype(dt).astype(jnp.float32)

        def rope(row, out_dt):                           # neox layout
            r1, r2 = row[:, :half], row[:, half:]
            out = row * c + jnp.concatenate([-r2, r1], axis=1) * s
            return out.astype(out_dt).astype(jnp.float32)

        # per-head dots, statically unrolled — same Mosaic constraint as
        # _decode_kernel's per-kv-head matmuls. q/k/v scratch rows hold
        # values already rounded the way the unfused composition rounds
        # them (q to the model dtype, k/v to the page dtype), so the
        # attention below reads what paged_attention would read.
        for i in range(h):
            q_s[i:i + 1, :] = rope(proj(wq_ref, i), dt)
        for i in range(kvh):
            k_s[i:i + 1, :] = rope(proj(wk_ref, i), ko_ref.dtype)
            v_s[i:i + 1, :] = proj(wv_ref, i).astype(
                vo_ref.dtype).astype(jnp.float32)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    row_ix = jax.lax.broadcasted_iota(jnp.int32, (page_size, d), 0)

    if writeback:
        # no dump page: every visited page is written back unchanged so
        # the aliased output never clobbers real pages with stale VMEM.
        # Invalid grid steps are redirected (in AND out) to the append
        # page; re-substituting the new row there keeps the write
        # idempotent whether the block it sees is pre- or post-append.
        ko_ref[...] = k_ref[...]
        vo_ref[...] = v_ref[...]
        append_here = (p == p_app) | (p * page_size > length)
    else:
        append_here = p == p_app

    @pl.when(append_here)
    def _append():
        # paged-KV append: substitute the new token's k/v row at
        # (p_app, off) — replicates PagedKVCache.update's s=1 scatter
        for i in range(kvh):
            kn = k_s[i:i + 1, :].astype(ko_ref.dtype)
            vn = v_s[i:i + 1, :].astype(vo_ref.dtype)
            ko_ref[0, :, i, :] = jnp.where(row_ix == off, kn,
                                           k_ref[0, :, i, :])
            vo_ref[0, :, i, :] = jnp.where(row_ix == off, vn,
                                           v_ref[0, :, i, :])

    # `<=` (not `<`): the append page must be addressable even when the
    # new token opens it (off == 0); positions past length are masked
    @pl.when(p * page_size <= length)
    def _accumulate():
        n = length + 1                   # post-append context length
        is_app = p == p_app
        s_parts = []
        for i in range(kvh):
            k_i = k_ref[0, :, i, :].astype(jnp.float32)
            k_i = jnp.where((row_ix == off) & is_app, k_s[i:i + 1, :], k_i)
            q_i = q_s[i * group:(i + 1) * group, :] * scale
            s_parts.append(jax.lax.dot_general(
                q_i, k_i, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        sc = jnp.concatenate(s_parts, axis=0)            # (H, page)
        pos = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) \
            + p * page_size
        sc = jnp.where(pos < n, sc, NEG_INF)
        m_prev = m_s[:, :]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(sc - m_new)
        l_s[:, :] = alpha * l_s[:, :] + jnp.sum(pr, axis=1, keepdims=True)
        m_s[:, :] = m_new
        pv_parts = []
        for i in range(kvh):
            v_i = v_ref[0, :, i, :].astype(jnp.float32)
            v_i = jnp.where((row_ix == off) & is_app, v_s[i:i + 1, :], v_i)
            pr_i = pr[i * group:(i + 1) * group, :]
            pv_parts.append(jax.lax.dot_general(
                pr_i, v_i, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_s[:, :] = alpha * acc_s[:, :] + jnp.concatenate(pv_parts,
                                                           axis=0)

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        dt = hmid_ref.dtype
        att = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).astype(dt)
        hidden = hmid_ref.shape[-1]
        o = jnp.zeros((1, hidden), jnp.float32)
        for i in range(h):
            o = o + jnp.dot(att[i:i + 1, :].astype(jnp.float32),
                            wo_ref[i * d:(i + 1) * d, :]
                            .astype(jnp.float32),
                            preferred_element_type=jnp.float32)
        hmid = x_ref[0] + o.astype(dt)   # residual add, model dtype
        hmid_ref[0] = hmid
        hf = hmid.astype(jnp.float32)    # post-attention rms_norm
        var2 = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
        y2 = (hf * jax.lax.rsqrt(var2 + eps2)).astype(dt) * ln2_ref[...]
        y2_ref[0] = y2


def fused_decode_layer(x, *, ln1_weight, ln1_eps, wq, wk, wv, wo,
                       rope_cos, rope_sin, ln2_weight, ln2_eps,
                       k_pages, v_pages, tables, lengths, heads,
                       attn_pages=None, dump_page=None, interpret=None):
    """One fused decode step for one decoder layer.

    x: (B, 1, hidden) layer input; lengths: (B,) int32 PRE-append
    depths; tables/pages as in ``paged_attention``; ``dump_page`` is the
    engine's sacrificial page id (static int) absorbing non-append page
    flushes — None falls back to in-place write-back.

    Returns ``(h_mid, y2, k_pages', v_pages')``: the post-attention
    residual state, its rms_norm (the MLP input — the MLP half stays in
    XLA), and the appended page pools. The caller advances
    ``cache.length`` by one.
    """
    b, _, hidden = x.shape
    npages, page_size, kvh, d = k_pages.shape
    if attn_pages is not None and attn_pages < tables.shape[1]:
        tables = tables[:, :attn_pages]
    pages_per_seq = tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    # the decode position IS the pre-append depth (offset semantics of
    # LlamaAttention.forward: positions = arange(1) + length). The rows
    # are gathered here, not by a (1, d) block over the (rows, d) table:
    # Mosaic wants a block's last two dims 8x128-aligned or whole, and a
    # (B, 1, d) operand read in (1, 1, d) blocks is whole.
    cos2 = rope_cos.reshape(-1, rope_cos.shape[-1])
    sin2 = rope_sin.reshape(-1, rope_sin.shape[-1])
    pos = jnp.clip(lengths.astype(jnp.int32), 0, cos2.shape[0] - 1)
    cos_b = cos2[pos].astype(x.dtype)[:, None, :]        # (B, 1, d)
    sin_b = sin2[pos].astype(x.dtype)[:, None, :]
    writeback = dump_page is None
    dump = 0 if writeback else int(dump_page)
    interp = _interpret() if interpret is None else interpret

    def x_map(bi, pi, tables_p, lens_p):
        return (bi, 0, 0)

    def w_map(bi, pi, tables_p, lens_p):
        return (0, 0)

    if writeback:
        # invalid steps read AND write the append page: the in-kernel
        # row re-substitution makes that write idempotent, so no page
        # ever receives stale content
        def kv_in_map(bi, pi, tables_p, lens_p):
            pid = jnp.where(pi * page_size <= lens_p[bi],
                            tables_p[bi, pi],
                            tables_p[bi, lens_p[bi] // page_size])
            return (jnp.clip(pid, 0, npages - 1), 0, 0, 0)

        kv_out_map = kv_in_map
    else:
        def kv_in_map(bi, pi, tables_p, lens_p):
            # `<=` admits the append page; table tails past the depth
            # may be uninitialized — redirect those (masked-anyway)
            # DMAs like paged_attention does
            pid = jnp.where(pi * page_size <= lens_p[bi],
                            tables_p[bi, pi], tables_p[bi, 0])
            return (jnp.clip(pid, 0, npages - 1), 0, 0, 0)

        def kv_out_map(bi, pi, tables_p, lens_p):
            pid = jnp.where(pi == lens_p[bi] // page_size,
                            tables_p[bi, pi], dump)
            return (jnp.clip(pid, 0, npages - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, hidden), x_map),
            pl.BlockSpec((1, hidden), w_map),            # ln1 weight
            pl.BlockSpec((1, hidden), w_map),            # ln2 weight
            pl.BlockSpec(wq.shape, w_map),
            pl.BlockSpec(wk.shape, w_map),
            pl.BlockSpec(wv.shape, w_map),
            pl.BlockSpec(wo.shape, w_map),
            pl.BlockSpec((1, 1, d), x_map),              # rope cos row
            pl.BlockSpec((1, 1, d), x_map),              # rope sin row
            pl.BlockSpec((1, page_size, kvh, d), kv_in_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_in_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, hidden), x_map),
            pl.BlockSpec((1, 1, hidden), x_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_out_map),
            pl.BlockSpec((1, page_size, kvh, d), kv_out_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, d), jnp.float32),   # roped q
            pltpu.VMEM((kvh, d), jnp.float32),     # new k row
            pltpu.VMEM((kvh, d), jnp.float32),     # new v row
            pltpu.VMEM((heads, 1), jnp.float32),   # running max
            pltpu.VMEM((heads, 1), jnp.float32),   # running denom
            pltpu.VMEM((heads, d), jnp.float32),   # running numerator
        ],
    )
    kernel = functools.partial(
        _megakernel, scale=scale, page_size=page_size,
        pages_per_seq=pages_per_seq, kvh=kvh, heads=heads,
        eps1=float(ln1_eps), eps2=float(ln2_eps), writeback=writeback)
    out_shape = [
        jax.ShapeDtypeStruct((b, 1, hidden), x.dtype),
        jax.ShapeDtypeStruct((b, 1, hidden), x.dtype),
        jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
        jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
    ]
    # page pools are aliased in/out: unwritten pages retain their
    # content (interpret mode honors the same retain semantics)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases={11: 2, 12: 3},
        name="decode_megakernel",
        interpret=interp,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), x,
      ln1_weight.reshape(1, -1), ln2_weight.reshape(1, -1),
      wq, wk, wv, wo, cos_b, sin_b, k_pages, v_pages)


def reference_decode_layer(x, *, ln1_weight, ln1_eps, wq, wk, wv, wo,
                           rope_cos, rope_sin, ln2_weight, ln2_eps,
                           k_pages, v_pages, tables, lengths, heads,
                           attn_pages=None, dump_page=None):
    """jnp oracle for the megakernel: the EXACT unfused serving decode
    composition (F.rms_norm jnp fallback -> Linear matmuls -> rope
    fallback gather -> PagedKVCache.update scatter -> interpret-mode
    paged attention -> o_proj -> residual -> rms_norm). Tests pin the
    Pallas kernel against this."""
    from .decode_attention import paged_attention

    b = x.shape[0]
    d = k_pages.shape[-1]
    kvh = k_pages.shape[2]
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xn = (xf * jax.lax.rsqrt(var + ln1_eps)).astype(dt) * ln1_weight
    q = (xn @ wq).reshape(b, 1, heads, d)
    k = (xn @ wk).reshape(b, 1, kvh, d)
    v = (xn @ wv).reshape(b, 1, kvh, d)
    cos2 = rope_cos.reshape(-1, rope_cos.shape[-1])
    sin2 = rope_sin.reshape(-1, rope_sin.shape[-1])
    pid = lengths[:, None]                         # (B, 1) position ids
    c = cos2.astype(dt)[pid][:, :, None, :]
    s = sin2.astype(dt)[pid][:, :, None, :]

    def rope(t):
        half = t.shape[-1] // 2
        t1, t2 = t[..., :half], t[..., half:]
        return t * c + jnp.concatenate([-t2, t1], axis=-1) * s

    q, k = rope(q), rope(k)
    page_size = k_pages.shape[1]
    page_ids = jnp.take_along_axis(
        tables, (lengths // page_size)[:, None], axis=1)[:, 0]
    off = lengths % page_size
    k_pages = k_pages.at[page_ids, off].set(k[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[page_ids, off].set(v[:, 0].astype(v_pages.dtype))
    out = paged_attention(q[:, 0], k_pages, v_pages, tables, lengths + 1,
                          pages_per_seq=attn_pages)
    attn_out = out.reshape(b, 1, -1) @ wo
    h_mid = x + attn_out
    hf = h_mid.astype(jnp.float32)
    var2 = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
    y2 = (hf * jax.lax.rsqrt(var2 + ln2_eps)).astype(dt) * ln2_weight
    return h_mid, y2, k_pages, v_pages
