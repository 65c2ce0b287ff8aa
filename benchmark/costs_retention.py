"""Operations and bytes of the power-retention model, from a configuration's
sizes and the counts the program made (``costs.py``'s rules: a multiply-add is
2, the embedding lookup is not a matmul, what the algorithm needs and not what
an implementation spends). The state is reckoned in its MINIMAL symmetric
form, d(d+1)/2 monomials a kv head, whatever layout the kernels keep.
"""
from __future__ import annotations

STATE_ITEMSIZE = 4           # the state is float32


def state_dim(m: dict) -> int:
    """Monomials of degree 2 in a head's d coordinates: d(d+1)/2."""
    d = m["head_dim"]
    return d * (d + 1) // 2


def layer_params(m: dict) -> int:
    """Matmul parameters of one decoder layer: q, k, v, o, the gate's map
    and the SwiGLU (norm weights and the gate's bias left out)."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    return (h * q + 2 * h * kv + q * h + h * m["num_key_value_heads"]
            + 3 * h * m["intermediate_size"])


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def retention_flops_token_layer(m: dict) -> float:
    """One token through one layer's recurrence: the update ``phi(k) v^T``
    of every kv head and the read ``phi(q)^T S`` of every query head."""
    return 2.0 * state_dim(m) * m["head_dim"] * (
        m["num_attention_heads"] + m["num_key_value_heads"])


def state_bytes_slot_layer(m: dict) -> int:
    """``S`` (D x d) and ``Z`` (D) of every kv head of one layer."""
    return (m["num_key_value_heads"] * state_dim(m) * (m["head_dim"] + 1)
            * STATE_ITEMSIZE)


def token_flops(m: dict) -> float:
    """A token through every layer, the head left out."""
    return m["num_hidden_layers"] * (2.0 * layer_params(m)
                                     + retention_flops_token_layer(m))


def prefill_flops(m: dict, tokens: int) -> float:
    return token_flops(m) * tokens


def decode_flops(m: dict, tokens: int) -> float:
    """Forward that produces ``tokens`` output tokens, head included."""
    return (token_flops(m) + 2.0 * head_params(m)) * tokens


def weight_bytes_step(m: dict, itemsize: int = 2) -> int:
    """What a decode step reads whatever its rows: every layer's matmul
    weights and the head (the embedded rows are noise beside them)."""
    return (m["num_hidden_layers"] * layer_params(m)
            + head_params(m)) * itemsize


def decode_step_min_s(m: dict, live_rows: float, peak: dict) -> float:
    """The least time of one decode step: the weights once, and every live
    row's state read and written once a layer."""
    byts = (weight_bytes_step(m) + live_rows * m["num_hidden_layers"] * 2
            * state_bytes_slot_layer(m))
    return byts / peak["hbm_bytes_per_s"]


def retention_decode_min_s(m: dict, live_rows: float, peak: dict) -> float:
    """The least time of ONE layer's decode kernel call: bound by bytes."""
    return (live_rows * 2 * state_bytes_slot_layer(m)
            / peak["hbm_bytes_per_s"])


def retention_chunk_min_s(m: dict, real_tokens: float, peak: dict) -> float:
    """The least time of ONE layer's share of a prefill's recurrence over
    ``real_tokens`` real tokens: bound by FLOPs."""
    return real_tokens * retention_flops_token_layer(m) / peak["bf16_flops"]
