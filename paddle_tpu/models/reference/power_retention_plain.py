"""Plain reference for a decoder whose every attention is POWER RETENTION
(Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239; Brumby-14B-Base is a Qwen3-14B-shaped decoder retrained
with it).

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: the ATTENTION form only on the path that judges (scores squared
and gated, a causal sum, rows in blocks so that ``[heads, T, T]`` fits at
T = 4,096); no kernel, no cache, no state, no feature map. It imports nothing
of the program; weights come to it by name
(``paddle_tpu.models.power_retention``'s parameter names) from a mapping or
from a function of the name.

Equations (``x`` (S, hidden) is the layer's input, ``h`` a query head, ``c =
h // (heads / kv heads)`` its kv head, ``d`` the head size, ``s = 1 /
sqrt(d)``, all sums over ``j <= t``; RMSNorm eps from the configuration):

    u      = rmsnorm(x)
    q_h    = rope(rmsnorm_head(W_q u)_h)     k_c = rope(rmsnorm_head(W_k u)_c)
    v_c    = (W_v u)_c
    log g_c,t = log_sigmoid((W_g u_t + b_g)_c)        float32, a gate a kv head
    a_h(t,j)  = exp(sum_{j<l<=t} log g_c,l) * (s q_h,t . k_c,j)^2     power 2
    y_h,t  = sum_j a_h(t,j) v_c,j / sum_j a_h(t,j)
    out    = x + W_o concat_h(y_h);  then  out + SwiGLU(rmsnorm(out))

RoPE is the half-rotation (neox) layout, position = row index; the final norm
and an untied head follow the last layer.

The same thing as a recurrence (``retention_recurrent``) and in chunks
(``retention_chunked``), which is what a serving engine keeps: with
``phi(z)`` the d(d+1)/2 monomials ``z_a z_b`` (a <= b, sqrt(2) where a < b)
``phi(q) . phi(k) = (q . k)^2``, so

    S_c,t = g_c,t S_c,t-1 + phi(s k_c,t) v_c,t^T     Z_c,t = g_c,t Z_c,t-1 + phi(s k_c,t)
    y_h,t = phi(q_h,t)^T S_c,t / phi(q_h,t)^T Z_c,t

Both are written here over the full ``d x d`` outer product ``k k^T`` (the
same function without a feature map), for the tests that hold the three forms
equal and for the control that reads what a state rounded to bfloat16 gives.

Departures from the published description: none known; what the published
``config.json`` does not carry (the power, the gate's form and its bias, the
normalisation, the state's precision) is listed under ``assumed`` in the
benchmark's configuration file.

``mm`` is the matmul of every linear projection, the gate's among them:
``f32`` for the reference proper, ``fp8`` for the control (the nearest
precision below the bfloat16 the configuration states). ``window`` plants the
fault of the rehearsal, "the carried state dropped at every chunk boundary":
a token then sees only the tokens of its own ``window``-sized chunk.
``state_dtype`` (chunked form) rounds the carried state at every boundary.

``end_state`` is what the recurrence holds after a number of tokens, a layer
(``forward_logits(..., state_at=n)`` gives it beside the logits): the exact
float32 sum, for the comparison that reads a serving engine's state back, or
the state as an engine that KEPT it in ``state_dtype`` would hold it -- rounded
where such an engine writes it, at every chunk boundary of the prompt and at
every token after.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROW_BLOCK = 512


def mm_f32(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def _to_fp8(x):
    """Per-tensor scaled float8_e4m3fn, given back in float32."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm_fp8(a, b):
    return jnp.matmul(_to_fp8(a), _to_fp8(b), precision=HI)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope_half(x, theta):
    """RoPE on ``x`` (S, heads, d), half-rotation layout, position = row."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = np.outer(np.arange(s, dtype=np.float64), inv)          # (S, d/2)
    cos = jnp.asarray(np.cos(ang), F32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), F32)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def projections(x, lw, m, mm):
    """(q (S, H, d), s k (S, KV, d), v (S, KV, d), log g (S, KV)) of the
    normed input ``x`` (S, hidden)."""
    s = x.shape[0]
    h, kv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    q = mm(x, lw["self_attn.q_proj.weight"]).reshape(s, h, d)
    k = mm(x, lw["self_attn.k_proj.weight"]).reshape(s, kv, d)
    v = mm(x, lw["self_attn.v_proj.weight"]).reshape(s, kv, d)
    q = rope_half(rms_norm(q, lw["self_attn.q_norm.weight"], eps), theta)
    k = rope_half(rms_norm(k, lw["self_attn.k_norm.weight"], eps), theta)
    gate = mm(x, lw["self_attn.g_proj.weight"]) \
        + lw["self_attn.g_bias"].astype(F32)
    return q, k / np.sqrt(d), v, jax.nn.log_sigmoid(gate)


def rounded(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s exponent and mantissa, given
    back in float32. An explicit ``reduce_precision``: a compiler that is
    allowed excess precision drops a pair of converts, and with it the
    control."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def retention_attention(q, k, v, logg, window=None):
    """The attention form: ``q`` (S, H, d), scaled ``k`` and ``v`` (S, KV,
    d), ``logg`` (S, KV) -> (S, H, d). Rows in blocks of ``ROW_BLOCK``."""
    s, h, d = q.shape
    kv = k.shape[1]
    grp = h // kv
    cum = jnp.cumsum(logg, axis=0)                               # (S, KV)
    qg = q.reshape(s, kv, grp, d)
    cols = jnp.arange(s)
    out = []
    for r0 in range(0, s, ROW_BLOCK):
        rows = jnp.arange(r0, min(r0 + ROW_BLOCK, s))
        sc = jnp.einsum("tcgd,jcd->cgtj", qg[rows], k, precision=HI)
        see = cols[None, :] <= rows[:, None]
        if window:
            see = see & (cols[None, :] // window == rows[:, None] // window)
        gap = cum[rows].T[:, :, None] - cum.T[:, None, :]        # (KV, t, j)
        a = jnp.where(see[None, None], jnp.exp(
            jnp.where(see[None], gap, -jnp.inf))[:, None] * sc * sc, 0.0)
        num = jnp.einsum("cgtj,jcd->tcgd", a, v, precision=HI)
        out.append(num / jnp.sum(a, -1).transpose(2, 0, 1)[..., None])
    return jnp.concatenate(out, 0).reshape(s, h, d)


def retention_recurrent(q, k, v, logg):
    """The recurrence, a token at a time, over the ``d x d`` outer product
    (``phi(q)^T S`` is ``q^T S q``): same arguments and result."""
    s, h, d = q.shape
    kv = k.shape[1]
    qg = q.reshape(s, kv, h // kv, d)

    def step(carry, t):
        st, z = carry
        g = jnp.exp(logg[t])                                     # (KV,)
        kk = k[t][:, :, None] * k[t][:, None, :]                 # (KV, d, d)
        st = g[:, None, None, None] * st + kk[..., None] * v[t][:, None,
                                                                None, :]
        z = g[:, None, None] * z + kk
        qq = qg[t][:, :, :, None] * qg[t][:, :, None, :]         # (KV, G, d, d)
        num = jnp.einsum("cgab,cabv->cgv", qq, st, precision=HI)
        den = jnp.einsum("cgab,cab->cg", qq, z, precision=HI)
        return (st, z), num / den[..., None]

    init = (jnp.zeros((kv, d, d, v.shape[-1]), F32), jnp.zeros((kv, d, d),
                                                               F32))
    _, y = jax.lax.scan(step, init, jnp.arange(s))
    return y.reshape(s, h, v.shape[-1])


def retention_chunked(q, k, v, logg, chunk, state_dtype=F32):
    """In chunks of ``chunk`` tokens: quadratic inside a chunk, the state
    ``(S, Z)`` carried between chunks (rounded to ``state_dtype`` at every
    boundary). Same arguments and result."""
    s, h, d = q.shape
    kv = k.shape[1]
    grp = h // kv
    st = jnp.zeros((kv, d, d, v.shape[-1]), F32)
    z = jnp.zeros((kv, d, d), F32)
    out = []
    for c0 in range(0, s, chunk):
        sl = slice(c0, min(c0 + chunk, s))
        qc = q[sl].reshape(-1, kv, grp, d)
        kc, vc = k[sl], v[sl]
        b = jnp.cumsum(logg[sl], axis=0)                         # (L, KV)
        n = qc.shape[0]
        see = jnp.tril(jnp.ones((n, n), bool))
        sc = jnp.einsum("tcgd,jcd->cgtj", qc, kc, precision=HI)
        gap = b.T[:, :, None] - b.T[:, None, :]
        a = jnp.where(see[None, None], jnp.exp(
            jnp.where(see[None], gap, -jnp.inf))[:, None] * sc * sc, 0.0)
        qq = qc[..., :, None] * qc[..., None, :]                 # (L,KV,G,d,d)
        eb = jnp.exp(b)[:, :, None]                              # (L, KV, 1)
        num = jnp.einsum("cgtj,jcd->tcgd", a, vc, precision=HI) \
            + eb[..., None] * jnp.einsum("tcgab,cabv->tcgv", qq, st,
                                         precision=HI)
        den = jnp.sum(a, -1).transpose(2, 0, 1) \
            + eb * jnp.einsum("tcgab,cab->tcg", qq, z, precision=HI)
        out.append(num / den[..., None])
        w = jnp.exp(b[-1][None, :] - b)                          # (L, KV)
        kk = kc[..., :, None] * kc[..., None, :] * w[..., None, None]
        end = jnp.exp(b[-1])
        st = end[:, None, None, None] * st + jnp.einsum(
            "jcab,jcv->cabv", kk, vc, precision=HI)
        z = end[:, None, None] * z + jnp.sum(kk, 0)
        st, z = rounded(st, state_dtype), rounded(z, state_dtype)
    return jnp.concatenate(out, 0).reshape(s, h, v.shape[-1])


def end_state(k, v, logg, n, prefill=None, chunk=None, state_dtype=F32,
              window=None):
    """``(S, Z)`` after the first ``n`` tokens, over the ``d x d`` outer
    product: ``S`` (KV, d, d, d_v), ``Z`` (KV, d, d); scaled ``k`` and ``v``
    (T, KV, d), ``logg`` (T, KV). ``n`` and ``prefill`` may be traced.

    In float32 it is the sum itself, ``sum_(j<n) exp(b_(n-1) - b_j) k_j
    k_j^T (x) v_j`` (with ``window``, the planted fault: over the tokens of
    the last one's own ``window``-sized chunk alone). In another
    ``state_dtype`` it is the recurrence as an engine that kept its state in
    that type would run it: the first ``prefill`` tokens in chunks of
    ``chunk`` and the rest a token at a time, rounded at every write."""
    t, kv, d = k.shape
    cum = jnp.cumsum(logg, axis=0)                               # (T, KV)

    def addend(start, length, lo, hi):
        # of the ``length`` tokens from ``start``, those in lo <= j < hi,
        # decayed to token hi - 1
        kc, vc, cc = (jax.lax.dynamic_slice_in_dim(a, start, length)
                      for a in (k, v, cum))
        p = start + jnp.arange(length)
        w = jnp.where(((p >= lo) & (p < hi))[:, None],
                      jnp.exp(cum[hi - 1][None, :] - cc), 0.0)
        kk = kc[..., :, None] * kc[..., None, :] * w[..., None, None]
        return (jnp.einsum("jcab,jcv->cabv", kk, vc, precision=HI),
                jnp.sum(kk, 0))

    st = jnp.zeros((kv, d, d, v.shape[-1]), F32)
    z = jnp.zeros((kv, d, d), F32)
    if jnp.dtype(state_dtype) == F32:
        lo = (n - 1) // window * window if window else 0
        for r0 in range(0, t, ROW_BLOCK):
            ds, dz = addend(r0, min(ROW_BLOCK, t - r0), lo, n)
            st, z = st + ds, z + dz
        return st, z
    if t % chunk:
        raise ValueError(f"{t} tokens are no whole number of chunks of "
                         f"{chunk}")

    def write(carry, start, length, lo, hi):
        # the state at token lo - 1 decayed to token hi - 1, plus the
        # tokens between, rounded as it is written
        dec = jnp.exp(cum[hi - 1] - jnp.where(lo > 0, cum[lo - 1], 0.0))
        ds, dz = addend(start, length, lo, hi)
        return (rounded(dec[:, None, None, None] * carry[0] + ds,
                        state_dtype),
                rounded(dec[:, None, None] * carry[1] + dz, state_dtype))

    carry = jax.lax.fori_loop(
        0, -(-prefill // chunk), lambda c, carry: write(
            carry, c * chunk, chunk, c * chunk,
            jnp.minimum((c + 1) * chunk, prefill)), (st, z))
    return jax.lax.fori_loop(
        prefill, n, lambda j, carry: write(carry, j, 1, j, j + 1), carry)


def swiglu(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def layer_forward(x, lw, m, mm, window=None, chunk=None, state_dtype=F32,
                  state_at=None, prefill=None):
    """One decoder layer on ``x`` (S, hidden), float32 in and out. With
    ``chunk`` the retention runs in its chunked form (for the control that
    rounds the carried state), else in the attention form. With
    ``state_at`` the result is ``(x, end_state(..., state_at, prefill))``."""
    eps = m["rms_norm_eps"]
    q, k, v, logg = projections(
        rms_norm(x, lw["input_layernorm.weight"], eps), lw, m, mm)
    if chunk:
        y = retention_chunked(q, k, v, logg, chunk, state_dtype)
    else:
        y = retention_attention(q, k, v, logg, window)
    state = None if state_at is None else end_state(
        k, v, logg, state_at, prefill, chunk, state_dtype, window)
    x = x + mm(y.reshape(x.shape[0], -1), lw["self_attn.o_proj.weight"])
    h = rms_norm(x, lw["post_attention_layernorm.weight"], eps)
    x = x + swiglu(h, lw["mlp.gate_proj.weight"], lw["mlp.up_proj.weight"],
                   lw["mlp.down_proj.weight"], mm)
    return x if state is None else (x, state)


LAYER_LEAVES = (
    "self_attn.q_proj.weight", "self_attn.k_proj.weight",
    "self_attn.v_proj.weight", "self_attn.o_proj.weight",
    "self_attn.q_norm.weight", "self_attn.k_norm.weight",
    "self_attn.g_proj.weight", "self_attn.g_bias",
    "mlp.gate_proj.weight", "mlp.up_proj.weight", "mlp.down_proj.weight",
    "input_layernorm.weight", "post_attention_layernorm.weight")


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool))))


@partial(jax.jit, static_argnames=("m", "mm", "window", "chunk",
                                   "state_dtype"))
def _layer_jit(x, lw, state_at=None, prefill=None, *, m, mm, window, chunk,
               state_dtype):
    return layer_forward(x, lw, dict(m), MATMULS[mm], window, chunk,
                         jnp.dtype(state_dtype), state_at, prefill)


@partial(jax.jit, static_argnames=("eps", "mm"))
def _head_jit(x_rows, norm_w, head_w, *, eps, mm):
    return MATMULS[mm](rms_norm(x_rows, norm_w, eps), head_w)


def forward_logits(weights, m: dict, ids, rows, mm: str = "f32", window=None,
                   chunk=None, state_dtype="float32", state_at=None,
                   prefill=None):
    """Logits (len(rows), vocab), float32, of the full causal forward over
    ``ids`` (S,) at the positions ``rows``. ``weights`` is a mapping from
    leaf name to array, or a function of the name (one layer's leaves are
    asked for, used and let go before the next layer's). ``ids`` may be
    padded at its end: a causal sum keeps padding out of earlier rows.

    With ``state_at`` = n the result is ``(logits, [(S, Z) a layer])``: what
    the recurrence holds after the first n tokens (:func:`end_state`; the
    first ``prefill`` of them came as a prompt, in chunks, where that
    matters)."""
    get = weights if callable(weights) else weights.__getitem__
    ids = jnp.asarray(ids, jnp.int32)
    x = get("model.embed_tokens.weight")[ids].astype(F32)
    frozen = _freeze(m)
    at = () if state_at is None else (jnp.int32(state_at), jnp.int32(
        state_at if prefill is None else prefill))
    states = []
    for i in range(m["num_hidden_layers"]):
        lw = {leaf: get(f"model.layers.{i}.{leaf}") for leaf in LAYER_LEAVES}
        x = _layer_jit(x, lw, *at, m=frozen, mm=mm, window=window,
                       chunk=chunk, state_dtype=state_dtype)
        if at:
            x, state = x
            states.append(state)
        del lw
    logits = _head_jit(x[jnp.asarray(rows)], get("model.norm.weight"),
                       get("lm_head.weight"), eps=m["rms_norm_eps"], mm=mm)
    return (logits, states) if at else logits
