"""Plain reference for the dense LLaMA-shaped decoder (Mistral, InternLM2).

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernels, no cache, no batching tricks. It imports nothing of the
program and takes nothing the program made; its weights come from
``benchmark/weights.py`` and the seed. Departures from the published models:
none in the equations (RMSNorm, rotate-half RoPE over ``head_dim``,
grouped-query causal attention, SwiGLU, untied head); InternLM2's fused
``wqkv`` is held as three projections, the same linear map.

``mm`` is the matmul the linear projections use: ``mm_f32`` for the
reference proper, ``mm_fp8`` for the control (the nearest precision below the
bfloat16 the configurations state), ``mm_bf16`` for tests.

Memory: a forward works layer by layer and returns logits only at the rows
asked for; the training step walks the layers backwards, updates each layer's
leaves as soon as its gradient exists, and never holds a whole gradient.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ------------------------------------------------------------------ matmuls

def mm_f32(a, b):
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def mm_bf16(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=F32)


def _to_fp8(x):
    """Per-tensor scaled float8_e4m3fn, given back in float32. Gradients
    pass straight through the rounding (a cast's own transpose would round
    the cotangents to fp8 too and flush most of them to nought)."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    low = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(low - x)


def mm_fp8(a, b):
    return jnp.matmul(_to_fp8(a), _to_fp8(b), precision=HI)


MATMULS = {"f32": mm_f32, "bf16": mm_bf16, "fp8": mm_fp8}


# ------------------------------------------------------------------- layers

def rope_tables(head_dim: int, n: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    ang = np.outer(np.arange(n, dtype=np.float64), inv)
    emb = np.concatenate([ang, ang], axis=-1)
    return jnp.asarray(np.cos(emb), F32), jnp.asarray(np.sin(emb), F32)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rotate(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


def layer_forward(x, lw, cos, sin, *, heads, kv_heads, eps, mm):
    """One decoder layer on ``x`` (B, S, H), float32 in and out. ``lw`` maps
    the layer's leaf names (``weights.LAYER_LEAVES`` and the two norms)."""
    b, s, _ = x.shape
    h = rms_norm(x, lw["input_layernorm"], eps)
    q = mm(h, lw["self_attn.q_proj"]).reshape(b, s, heads, -1)
    k = mm(h, lw["self_attn.k_proj"]).reshape(b, s, kv_heads, -1)
    v = mm(h, lw["self_attn.v_proj"]).reshape(b, s, kv_heads, -1)
    d = q.shape[-1]
    q, k = _rotate(q, cos[:s], sin[:s]), _rotate(k, cos[:s], sin[:s])
    rep = heads // kv_heads
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HI)
    x = x + mm(att.reshape(b, s, heads * d), lw["self_attn.o_proj"])
    h = rms_norm(x, lw["post_attention_layernorm"], eps)
    gate = mm(h, lw["mlp.gate_proj"])
    up = mm(h, lw["mlp.up_proj"])
    return x + mm(jax.nn.silu(gate) * up, lw["mlp.down_proj"])


def layer_weights(weights: dict, i: int) -> dict:
    pre = f"model.layers.{i}."
    return {k[len(pre):-len(".weight")]: v for k, v in weights.items()
            if k.startswith(pre)}


def _static(m: dict) -> dict:
    return dict(heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], eps=m["rms_norm_eps"])


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "mm"))
def _layer_jit(x, lw, cos, sin, *, heads, kv_heads, eps, mm):
    return layer_forward(x, lw, cos, sin, heads=heads, kv_heads=kv_heads,
                         eps=eps, mm=MATMULS[mm])


@partial(jax.jit, static_argnames=("eps", "mm"))
def _head_jit(x_rows, norm_w, head_w, *, eps, mm):
    return MATMULS[mm](rms_norm(x_rows, norm_w, eps), head_w)


def forward_logits(weights: dict, m: dict, ids: np.ndarray, rows: np.ndarray,
                   mm: str = "f32"):
    """Logits (len(rows), vocab), float32, of the full causal forward over
    ``ids`` (S,) at the positions ``rows``. ``ids`` may be padded at its end:
    causal attention keeps padding out of earlier rows."""
    ids = jnp.asarray(ids, jnp.int32)[None]
    cos, sin = rope_tables(m["head_dim"], ids.shape[1], m["rope_theta"])
    x = weights["model.embed_tokens.weight"][ids].astype(F32)
    for i in range(m["num_hidden_layers"]):
        x = _layer_jit(x, layer_weights(weights, i), cos, sin, mm=mm,
                       **_static(m))
    return _head_jit(x[0, jnp.asarray(rows)], weights["model.norm.weight"],
                     weights["lm_head.weight"], eps=m["rms_norm_eps"], mm=mm)


# ----------------------------------------------------------------- training

def _bf16_round(w):
    """The weight the step computes with: the float32 master rounded to the
    bfloat16 the configuration states, held in float32."""
    return w.astype(jnp.bfloat16).astype(F32)


def _loss_rows(x_rows, norm_w, head_w, labels, n_total, eps, mm):
    logits = MATMULS[mm](rms_norm(x_rows, norm_w, eps), head_w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked) / n_total


@partial(jax.jit, static_argnames=("eps", "mm", "n_total"))
def _head_grad_jit(x_rows, norm_w, head_w, labels, *, n_total, eps, mm):
    return jax.value_and_grad(_loss_rows, argnums=(0, 1, 2))(
        x_rows, norm_w, head_w, labels, n_total, eps, mm)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "mm"))
def _layer_vjp_jit(x, lw, cos, sin, g, *, heads, kv_heads, eps, mm):
    _, vjp = jax.vjp(lambda x_, lw_: layer_forward(
        x_, lw_, cos, sin, heads=heads, kv_heads=kv_heads, eps=eps,
        mm=MATMULS[mm]), x, lw)
    return vjp(g)


@partial(jax.jit, static_argnames=("hp",), donate_argnums=(0, 1, 2))
def _adamw_jit(master, m1, m2, g, t, *, hp):
    """AdamW as the configuration states it: decoupled decay, float32
    arithmetic on the float32 master, moments stored in ``moment_dtype``."""
    lr, b1, b2, eps, wd, mdt = hp
    g = g.astype(F32)
    p = master * (1.0 - lr * wd)
    m1n = b1 * m1.astype(F32) + (1 - b1) * g
    m2n = b2 * m2.astype(F32) + (1 - b2) * g * g
    mhat = m1n / (1 - b1 ** t)
    vhat = m2n / (1 - b2 ** t)
    new = p - lr * mhat / (jnp.sqrt(vhat) + eps)
    return new, m1n.astype(mdt), m2n.astype(mdt)


_norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))))


class PlainTrainer:
    """The first steps of training, plainly: float32 masters made from the
    seeded bfloat16 weights, loss = mean next-token cross-entropy over the
    ``batch * (seq - 1)`` shifted positions, AdamW per leaf.

    ``half_batch`` is the planted fault of the control tests: the second
    half of the rows is left out and the mean is taken over the rest."""

    def __init__(self, weights: dict, m: dict, train: dict, mm: str = "f32",
                 half_batch: bool = False):
        self.m, self.mm, self.half = m, mm, half_batch
        self.master = {k: jnp.array(v, dtype=F32) for k, v in weights.items()}
        mdt = jnp.dtype(train["moment_dtype"])
        self.m1 = {k: jnp.zeros(v.shape, mdt) for k, v in self.master.items()}
        self.m2 = {k: jnp.zeros(v.shape, mdt) for k, v in self.master.items()}
        self.hp = (float(train["lr"]), float(train["beta1"]),
                   float(train["beta2"]), float(train["epsilon"]),
                   float(train["weight_decay"]), mdt)
        self.t = 0
        self.grad_norms = None

    def _update(self, name, g):
        if self.t == 1:
            self._gn[name] = _norm(g)
        self.master[name], self.m1[name], self.m2[name] = _adamw_jit(
            self.master[name], self.m1[name], self.m2[name], g,
            jnp.asarray(self.t, F32), hp=self.hp)

    def step(self, ids: np.ndarray) -> float:
        """One step on ``ids`` (batch, seq); returns the loss."""
        m, mm = self.m, self.mm
        if self.half:
            ids = ids[: max(1, ids.shape[0] // 2)]
        self.t += 1
        if self.t == 1:
            self._gn = {}
        ids = jnp.asarray(ids, jnp.int32)
        b, s = ids.shape
        cos, sin = rope_tables(m["head_dim"], s, m["rope_theta"])
        st = _static(m)
        nl = m["num_hidden_layers"]
        emb = _bf16_round(self.master["model.embed_tokens.weight"])
        xs = [[emb[ids[r:r + 1]] for r in range(b)]]   # one row at a time
        del emb
        for i in range(nl):
            lw = {k: _bf16_round(v)
                  for k, v in layer_weights(self.master, i).items()}
            xs.append([_layer_jit(x, lw, cos, sin, mm=mm, **st)
                       for x in xs[-1]])
            del lw
        n_total = b * (s - 1)
        labels = ids[:, 1:]
        norm_w = _bf16_round(self.master["model.norm.weight"])
        head_w = _bf16_round(self.master["lm_head.weight"])
        loss = 0.0
        g = []
        g_norm = jnp.zeros_like(norm_w)
        g_head = jnp.zeros_like(head_w)
        for r in range(b):
            l, (gx, gn, gh) = _head_grad_jit(
                xs[-1][r][0, :-1], norm_w, head_w, labels[r],
                n_total=n_total, eps=m["rms_norm_eps"], mm=mm)
            loss = loss + l
            g.append(jnp.concatenate([gx, jnp.zeros_like(gx[:1])], 0)[None])
            g_norm, g_head = g_norm + gn, g_head + gh
        del norm_w, head_w
        self._update("model.norm.weight", g_norm)
        self._update("lm_head.weight", g_head)
        del g_norm, g_head
        for i in reversed(range(nl)):
            lw = {k: _bf16_round(v)
                  for k, v in layer_weights(self.master, i).items()}
            g_lw = None
            for r in range(b):
                g[r], g_row = _layer_vjp_jit(xs[i][r], lw, cos, sin, g[r],
                                             mm=mm, **st)
                g_lw = g_row if g_lw is None else jax.tree_util.tree_map(
                    jnp.add, g_lw, g_row)
            del lw, g_row
            xs.pop()
            for leaf, gl in g_lw.items():
                self._update(f"model.layers.{i}.{leaf}.weight", gl)
            del g_lw
        g_emb = jnp.zeros(self.master["model.embed_tokens.weight"].shape, F32
                          ).at[ids.reshape(-1)].add(
            jnp.concatenate(g).reshape(b * s, -1))
        self._update("model.embed_tokens.weight", g_emb)
        if self.t == 1:
            self.grad_norms = {k: float(v) for k, v in self._gn.items()}
        return float(loss)

    def change_norms(self, start: dict) -> dict:
        """Per leaf, the norm of (master now - ``start``), the seeded
        weights the caller makes again (no copy is kept through the steps)."""
        return {k: float(_norm(self.master[k] - start[k].astype(F32)))
                for k in self.master}
