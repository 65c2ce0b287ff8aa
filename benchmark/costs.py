"""Operations and bytes from shapes, and the table of peaks.

The yardstick: every roofline share and every ``mfu`` is computed here from a
configuration's sizes and counts the harness made, never from a default. Model
FLOPs are what the algorithm needs: a multiply-add is 2, recomputation is not
counted, the embedding lookup is not a matmul.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown device is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json; add it with its source")
    return table[device_kind]


def layer_params(m: dict) -> int:
    """Matmul parameters of one decoder layer (norm weights left out)."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + 3 * h * m["intermediate_size"]


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def matmul_params(m: dict) -> int:
    """N without the embedding table: the layers and the output head."""
    return m["num_hidden_layers"] * layer_params(m) + head_params(m)


def attn_flops_token(m: dict, context: int) -> float:
    """QK^T and PV for one query token over ``context`` keys, all layers."""
    return (4.0 * m["num_attention_heads"] * m["head_dim"] * context
            * m["num_hidden_layers"])


def attn_flops_span(m: dict, start: int, stop: int) -> float:
    """Causal attention for the tokens at positions ``start .. stop-1``
    (position p attends to p + 1 keys), all layers."""
    n = stop - start
    keys = n * start + n * (n + 1) / 2.0
    return (4.0 * m["num_attention_heads"] * m["head_dim"] * keys
            * m["num_hidden_layers"])


def prefill_flops(m: dict, start: int, stop: int) -> float:
    """Forward of prompt tokens ``start .. stop-1``: the layers for every
    token (the head is needed only where a token is produced)."""
    return (2.0 * m["num_hidden_layers"] * layer_params(m) * (stop - start)
            + attn_flops_span(m, start, stop))


def decode_flops(m: dict, start: int, stop: int) -> float:
    """Forward that produces the tokens at positions ``start .. stop-1``:
    layers and head for each, attention over the context before it."""
    n = stop - start
    return 2.0 * matmul_params(m) * n + attn_flops_span(m, start - 1, stop - 1)


def train_flops_tokens(m: dict, batch: int, seq: int) -> float:
    """Forward + backward of one step on ``batch`` sequences of ``seq``
    tokens: 6 N per token and three times the causal attention."""
    return batch * (6.0 * matmul_params(m) * seq
                    + 3.0 * attn_flops_span(m, 0, seq))


def weight_bytes_step(m: dict, itemsize: int = 2) -> int:
    """Bytes of weights one decode step has to read: every layer and the
    head (norm weights and the embedded rows are noise beside them)."""
    return matmul_params(m) * itemsize


def kv_bytes_token(m: dict, itemsize: int = 2) -> int:
    """K and V of one token over all layers."""
    return (2 * m["num_key_value_heads"] * m["head_dim"] * itemsize
            * m["num_hidden_layers"])


def decode_step_min_s(m: dict, live_tokens: float, peak: dict) -> float:
    """The least time of one decode step: weights and live KV from HBM."""
    return ((weight_bytes_step(m) + kv_bytes_token(m) * live_tokens)
            / peak["hbm_bytes_per_s"])


def paged_attn_min_s(m: dict, live_tokens: float, slots: int,
                     peak: dict) -> float:
    """The least time of ONE layer's paged decode attention over
    ``live_tokens`` cached tokens in ``slots`` sequences: the larger of its
    bytes over the HBM rate and its operations over the peak."""
    d, hq = m["head_dim"], m["num_attention_heads"]
    byts = (kv_bytes_token(m) / m["num_hidden_layers"] * live_tokens
            + 2 * slots * hq * d * 2)
    flops = 4.0 * hq * d * live_tokens
    return max(byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])


def flash_min_s(m: dict, batch: int, seq: int, peak: dict,
                backward: bool = False) -> float:
    """The least time of ONE layer's causal flash attention over ``batch``
    sequences of ``seq`` tokens: forward 4 h d S^2 / 2 operations, backward
    2.5 times that (dQ, dK, dV and the recomputed scores); bytes are q, k,
    v, o (and their gradients) once."""
    d, hq, hkv = m["head_dim"], m["num_attention_heads"], \
        m["num_key_value_heads"]
    flops = batch * 4.0 * hq * d * seq * (seq + 1) / 2.0
    byts = batch * seq * d * 2 * (2 * hq + 2 * hkv)
    if backward:
        flops, byts = 2.5 * flops, 2.0 * byts + batch * seq * hq * d * 2
    return max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
