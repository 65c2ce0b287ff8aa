"""Plain reference for the hybrid decoder of the ``nemotron_h`` family (NVIDIA
Nemotron-H / Nemotron 3 Nano, arXiv:2504.03624): Mamba-2 layers, softmax
attention layers and sparse-expert layers in one stack.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: the Mamba-2 layers as the RECURRENCE, a token at a time; plain
causal attention in row blocks; a loop over the experts (every held expert
multiplies every token and a token keeps the ones it chose); no kernel, no
cache, no chunked form on the path that judges. It imports nothing of the
program; weights come to it by name (``paddle_tpu.models.nemotron_h``'s
parameter names) from a mapping or from a function of the name, so a caller
can make one layer's leaves at a time.

Equations (``x`` (S, hidden); every layer is ``x += Mixer_i(rmsnorm(x))``, eps
``layer_norm_epsilon``, the mixer named by character i of
``hybrid_override_pattern``; then a final norm and an untied head):

* ``M``, Mamba-2 (H heads of P channels, G groups, state N, kernel K):
  ``[z | xBC | dt] = u W_in`` (H P | H P + 2 G N | H); ``xBC = silu(conv_K(xBC)
  + b)`` (causal, depthwise: ``out_t = sum_k w_k in_(t+k-K+1)``), split into
  ``x`` (H, P), ``B``, ``C`` (G, N; head h reads group h // (H / G)); ``dt =
  softplus(dt + dt_bias)``; ``a = exp(-dt exp(A_log))``. ``S_t = a_t S_(t-1)
  + dt_t x_t B_t^T`` (P x N a head); ``y_t = S_t C_t + D x_t``; ``y = y *
  silu(z)``, RMS-normalised in G groups of H P / G channels, times the norm's
  weight; output ``y W_out``.
* ``*``: 32 query heads over 2 kv heads, causal softmax at ``1 /
  sqrt(head_dim)``, no position embedding.
* ``E``: ``s = sigmoid(u W_g)``; the ``num_experts_per_tok`` largest of ``s +
  b`` are chosen; weights ``s_k / sum s`` times ``routed_scaling_factor``;
  ``y = sum_k w_k E_k(u) + E_shared(u)``, ``E(u) = relu(u W_up)^2 W_down``.

The same state-space layer in chunks (``ssd_chunked``: the products inside a
chunk under the decay mask, the carried state in and out) is here for the
test that holds the two forms equal; it judges nothing.

Departures from the published description: none known. What the published
``config.json`` leaves open (no rotary; the initialisation of ``A_log`` and
``dt_bias``) is listed under ``assumed`` in the benchmark's configuration.

``mm`` is the matmul of every linear projection, the router's among them:
``f32`` for the reference proper, ``fp8`` for the control (the nearest
precision below the bfloat16 the configuration states), ``bf16`` for the
witness (the configuration's own precision in the reference's place: what a
sound bfloat16 computation with a float32 state reads). ``experts_held =
(first, count)`` gives the reference the same share of the routed experts as
a chip holds. ``window`` plants the fault of the rehearsal, "what a Mamba-2
layer carries (state and convolution inputs) dropped at every chunk
boundary". ``state_dtype`` with ``chunk`` keeps the state as an engine that
KEPT it in that type would: rounded where such an engine writes it, at every
chunk boundary of the prompt and at every token after.

``forward_logits(..., state_at=n)`` gives beside the logits what each
Mamba-2 layer holds after the first n tokens: ``(S (H, P, N), the last K - 1
convolution inputs (K - 1, H P + 2 G N))``.
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


def mm_bf16(a, b):
    """The witness: inputs and result rounded to bfloat16, the sum in
    float32, as the program's projections are. A sound computation at the
    configuration's own precision: where it reads as far from the float32
    reference as the program does, that distance is the precision's."""
    a, b = (rounded(x.astype(F32), jnp.bfloat16) for x in (a, b))
    return rounded(jnp.matmul(a, b, precision=HI), jnp.bfloat16)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8, "bf16": mm_bf16}


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rounded(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s exponent and mantissa, given
    back in float32. An explicit ``reduce_precision``: a compiler that is
    allowed excess precision drops a pair of converts, and with it the
    control."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


# ------------------------------------------------------------------- Mamba-2

def mamba_inputs(u, lw, m, mm, window=None):
    """Everything ahead of the recurrence, from the normed input ``u`` (S,
    hidden): ``(z (S, H P), xBC before the convolution (S, C), x (S, H, P),
    B, C (S, G, N), dt (S, H), the log-decay -dt exp(A_log) (S, H))``. With
    ``window`` the convolution sees no input of an earlier chunk."""
    h, p, g, n = (m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"],
                  m["ssm_state_size"])
    inner, k = h * p, m["conv_kernel"]
    s = u.shape[0]
    proj = mm(u, lw["mixer.in_proj.weight"])
    z, raw, dt = (proj[:, :inner], proj[:, inner:inner + inner + 2 * g * n],
                  proj[:, inner + inner + 2 * g * n:])
    w = lw["mixer.conv_weight"].astype(F32)                      # (K, C)
    padded = jnp.concatenate([jnp.zeros((k - 1, raw.shape[1]), F32), raw])
    t = jnp.arange(s)
    conv = lw["mixer.conv_bias"].astype(F32)
    for i in range(k):
        src = t + i - (k - 1)            # the input this tap reads
        tap = padded[i:i + s]
        if window:
            tap = jnp.where((src // window == t // window)[:, None], tap, 0.0)
        conv = conv + w[i] * tap
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, h, p)
    bm = xbc[:, inner:inner + g * n].reshape(s, g, n)
    cm = xbc[:, inner + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + lw["mixer.dt_bias"].astype(F32))
    return z, raw, x, bm, cm, dt, -dt * jnp.exp(lw["mixer.A_log"].astype(F32))


def ssd_recurrent(x, dt, da, bm, cm, window=None, chunk=None,
                  state_dtype=F32, state_at=None, prefill=None):
    """The recurrence, a token at a time: ``x`` (S, H, P), ``dt`` and the
    log-decay ``da`` (S, H), ``bm`` / ``cm`` (S, G, N) -> ``(y (S, H, P),
    the state after ``state_at`` tokens or None)``. ``window``: the state
    is dropped at every multiple of it. ``state_dtype`` other than float32:
    the state is rounded at every ``chunk`` boundary of the first
    ``prefill`` tokens, at their end, and at every token after."""
    s, h, p = x.shape
    rep = h // bm.shape[1]
    rounding = jnp.dtype(state_dtype) != F32
    at = -1 if state_at is None else state_at

    def step(carry, t):
        st, snap = carry
        if window:
            st = jnp.where(t % window == 0, 0.0, st)
        bh, ch = jnp.repeat(bm[t], rep, 0), jnp.repeat(cm[t], rep, 0)
        st = (jnp.exp(da[t])[:, None, None] * st
              + (dt[t][:, None] * x[t])[..., None] * bh[:, None, :])
        y = jnp.einsum("hpn,hn->hp", st, ch, precision=HI)
        if rounding:
            write = (t >= prefill - 1) | ((t + 1) % chunk == 0)
            st = jnp.where(write, rounded(st, state_dtype), st)
        snap = jnp.where(t == at - 1, st, snap)
        return (st, snap), y

    zero = jnp.zeros((h, p, bm.shape[2]), F32)
    (_, snap), y = jax.lax.scan(step, (zero, zero), jnp.arange(s))
    return y, (None if state_at is None else snap)


def ssd_chunked(x, dt, da, bm, cm, chunk):
    """The same function in chunks of ``chunk`` tokens: inside a chunk the
    products ``C_t . B_j`` under the decay mask ``exp(cum_t - cum_j)`` (j <=
    t) times ``dt_j`` against ``x``; the carried state read through ``C`` at
    ``exp(cum_t)``; the state at the chunk's end carried on. -> ``y``."""
    s, h, p = x.shape
    rep = h // bm.shape[1]
    st = jnp.zeros((h, p, bm.shape[2]), F32)
    out = []
    for c0 in range(0, s, chunk):
        sl = slice(c0, min(c0 + chunk, s))
        xc, dtc = x[sl], dt[sl]
        bh, ch = jnp.repeat(bm[sl], rep, 1), jnp.repeat(cm[sl], rep, 1)
        cum = jnp.cumsum(da[sl], axis=0)                         # (L, H)
        n = xc.shape[0]
        see = jnp.tril(jnp.ones((n, n), bool))[:, :, None]
        gap = cum[:, None, :] - cum[None, :, :]                  # (t, j, H)
        w = jnp.where(see, jnp.exp(jnp.where(see, gap, 0.0)), 0.0) \
            * jnp.einsum("thn,jhn->tjh", ch, bh, precision=HI) * dtc[None]
        out.append(jnp.einsum("tjh,jhp->thp", w, xc, precision=HI)
                   + jnp.exp(cum)[..., None] * jnp.einsum(
                       "thn,hpn->thp", ch, st, precision=HI))
        tail = jnp.exp(cum[-1][None] - cum) * dtc
        st = jnp.exp(cum[-1])[:, None, None] * st + jnp.einsum(
            "jhp,jhn->hpn", xc * tail[..., None], bh, precision=HI)
    return jnp.concatenate(out, 0)


def mamba_mixer(u, lw, m, mm, window=None, chunk=None, state_dtype=F32,
                state_at=None, prefill=None):
    """One Mamba-2 mixer on the normed input ``u``; with ``state_at`` the
    result is ``(out, (S, carried convolution inputs))``."""
    h, p, g = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"]
    k = m["conv_kernel"]
    s = u.shape[0]
    z, raw, x, bm, cm, dt, da = mamba_inputs(u, lw, m, mm, window)
    y, state = ssd_recurrent(x, dt, da, bm, cm, window, chunk, state_dtype,
                             state_at, prefill)
    y = y + lw["mixer.D"].astype(F32)[:, None] * x
    y = y.reshape(s, h * p) * jax.nn.silu(z)
    yg = y.reshape(s, g, h * p // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + m["layer_norm_epsilon"])
    y = yg.reshape(s, h * p) * lw["mixer.norm_weight"].astype(F32)
    out = mm(y, lw["mixer.out_proj.weight"])
    if state_at is None:
        return out
    padded = jnp.concatenate([jnp.zeros((k - 1, raw.shape[1]), F32), raw])
    return out, (state, jax.lax.dynamic_slice_in_dim(padded, state_at,
                                                     k - 1))


# ----------------------------------------------------------------- attention

def attention(u, lw, m, mm):
    """Causal grouped-query softmax attention, rows in blocks; no position
    embedding."""
    s = u.shape[0]
    h, kv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    q = mm(u, lw["mixer.q_proj.weight"]).reshape(s, kv, h // kv, d)
    k = mm(u, lw["mixer.k_proj.weight"]).reshape(s, kv, d)
    v = mm(u, lw["mixer.v_proj.weight"]).reshape(s, kv, d)
    cols = jnp.arange(s)
    out = []
    for r0 in range(0, s, ROW_BLOCK):
        rows = jnp.arange(r0, min(r0 + ROW_BLOCK, s))
        sc = jnp.einsum("tcgd,jcd->cgtj", q[rows], k, precision=HI) \
            / np.sqrt(d)
        sc = jnp.where(cols[None, :] <= rows[:, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("cgtj,jcd->tcgd", pr, v, precision=HI))
    return mm(jnp.concatenate(out, 0).reshape(s, h * d),
              lw["mixer.o_proj.weight"])


# ------------------------------------------------------------------- experts

def relu2_mlp(x, w_up, w_down, mm):
    return mm(jnp.square(jax.nn.relu(mm(x, w_up))), w_down)


def router(u, lw, m, mm):
    """(chosen ids, weights), each (S, top_k): choose by ``s + b``, weigh
    by ``s``."""
    s = jax.nn.sigmoid(mm(u, lw["mixer.gate.weight"]))
    _, ids = jax.lax.top_k(
        s + lw["mixer.e_score_correction_bias"].astype(F32),
        m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    if m.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * m["routed_scaling_factor"]


def routed_experts(u, lw, m, mm, experts_held=None):
    """``sum_k w_k E_k(u)`` over the experts of ``experts_held`` (all by
    default): a loop over those experts, each multiplying every token. The
    first stack may carry columns past the expert's width (the program pads
    them to a lane multiple with zeros): they are not read."""
    first, count = experts_held or (0, m["n_routed_experts"])
    f = m["moe_intermediate_size"]
    ids, w = router(u, lw, m, mm)
    dense_w = jnp.zeros((u.shape[0], m["n_routed_experts"]), F32).at[
        jnp.arange(u.shape[0])[:, None], ids].add(w)
    up, down = lw["mixer.experts_up"], lw["mixer.experts_down"]

    def one(acc, e):
        y = relu2_mlp(u, up[e][:, :f], down[e], mm)
        return acc + jax.lax.dynamic_slice_in_dim(
            dense_w, first + e, 1, axis=1) * y, None

    out, _ = jax.lax.scan(one, jnp.zeros(u.shape, F32), jnp.arange(count))
    return out


def expert_mixer(u, lw, m, mm, experts_held=None, shared=True):
    out = routed_experts(u, lw, m, mm, experts_held)
    if shared:
        out = out + relu2_mlp(u, lw["mixer.shared_experts.up_proj.weight"],
                              lw["mixer.shared_experts.down_proj.weight"], mm)
    return out


# --------------------------------------------------------------- the forward

LEAVES = {
    "M": ("norm.weight", "mixer.in_proj.weight", "mixer.conv_weight",
          "mixer.conv_bias", "mixer.A_log", "mixer.D", "mixer.dt_bias",
          "mixer.norm_weight", "mixer.out_proj.weight"),
    "*": ("norm.weight", "mixer.q_proj.weight", "mixer.k_proj.weight",
          "mixer.v_proj.weight", "mixer.o_proj.weight"),
    "E": ("norm.weight", "mixer.gate.weight", "mixer.e_score_correction_bias",
          "mixer.experts_up", "mixer.experts_down",
          "mixer.shared_experts.up_proj.weight",
          "mixer.shared_experts.down_proj.weight"),
}


def layer_forward(x, lw, m, mm, kind, experts_held=None, window=None,
                  chunk=None, state_dtype=F32, state_at=None, prefill=None):
    """One layer on ``x`` (S, hidden), float32 in and out. A Mamba-2 layer
    with ``state_at`` gives ``(x, (S, carried inputs))``."""
    u = rms_norm(x, lw["norm.weight"], m["layer_norm_epsilon"])
    if kind == "M":
        out = mamba_mixer(u, lw, m, mm, window, chunk, state_dtype, state_at,
                          prefill)
        if state_at is not None:
            return x + out[0], out[1]
        return x + out
    if kind == "*":
        return x + attention(u, lw, m, mm)
    return x + expert_mixer(u, lw, m, mm, experts_held)


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, bool, str))))


@partial(jax.jit, static_argnames=("m", "mm", "kind", "experts_held",
                                   "window", "chunk", "state_dtype"))
def _layer_jit(x, lw, state_at=None, prefill=None, *, m, mm, kind,
               experts_held, window, chunk, state_dtype):
    return layer_forward(x, lw, dict(m), MATMULS[mm], kind, experts_held,
                         window, chunk, jnp.dtype(state_dtype), state_at,
                         prefill)


@partial(jax.jit, static_argnames=("eps", "mm"))
def _head_jit(x_rows, norm_w, head_w, *, eps, mm):
    return MATMULS[mm](rms_norm(x_rows, norm_w, eps), head_w)


def forward_logits(weights, m: dict, ids, rows, mm: str = "f32",
                   experts_held=None, window=None, chunk=None,
                   state_dtype="float32", state_at=None, prefill=None):
    """Logits (len(rows), vocab), float32, of the full causal forward over
    ``ids`` (S,) at the positions ``rows``. ``weights`` is a mapping from
    leaf name to array, or a function of the name (one layer's leaves are
    asked for, used and let go before the next layer's). ``ids`` may be
    padded at its end: every layer is causal, so padding stays out of
    earlier rows.

    With ``state_at`` = n the result is ``(logits, [(S, carried inputs) a
    Mamba-2 layer])``: what each holds after the first n tokens (the first
    ``prefill`` of them came as a prompt, in chunks, where that matters)."""
    get = weights if callable(weights) else weights.__getitem__
    ids = jnp.asarray(ids, jnp.int32)
    x = get("model.embed_tokens.weight")[ids].astype(F32)
    frozen = _freeze(m)
    held = tuple(experts_held) if experts_held is not None else None
    at = () if state_at is None else (jnp.int32(state_at), jnp.int32(
        state_at if prefill is None else prefill))
    states = []
    for i, kind in enumerate(m["hybrid_override_pattern"]):
        lw = {leaf: get(f"model.layers.{i}.{leaf}") for leaf in LEAVES[kind]}
        x = _layer_jit(x, lw, *(at if kind == "M" else ()), m=frozen, mm=mm,
                       kind=kind, experts_held=held, window=window,
                       chunk=chunk, state_dtype=state_dtype)
        if at and kind == "M":
            x, state = x
            states.append(state)
        del lw
    logits = _head_jit(x[jnp.asarray(rows)], get("model.norm.weight"),
                       get("lm_head.weight"), eps=m["layer_norm_epsilon"],
                       mm=mm)
    return (logits, states) if at else logits
