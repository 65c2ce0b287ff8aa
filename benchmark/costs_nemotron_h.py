"""Operations and bytes of the ``nemotron_h`` hybrid model, from a
configuration's sizes and the counts the program made (``costs.py``'s rules:
a multiply-add is 2, the embedding lookup is not a matmul, what the algorithm
needs and not what an implementation spends).

``m`` is the configuration's model section with ``experts_held`` beside it
(``program_nemotron_h.model_section``). A layer is ONE mixer, named by its
character of ``hybrid_override_pattern``: ``M`` Mamba-2, ``*`` attention,
``E`` experts.
"""
from __future__ import annotations

STATE_ITEMSIZE = 4           # the state and the carried inputs are float32


def layers(m: dict, kind: str) -> int:
    return m["hybrid_override_pattern"].count(kind)


def mamba_inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_dim(m: dict) -> int:
    return mamba_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mamba_params(m: dict) -> int:
    """Matmul parameters of one Mamba-2 layer: ``in_proj`` and ``out_proj``
    (the convolution's 4 taps a channel are no matmul)."""
    h = m["hidden_size"]
    return (h * (mamba_inner(m) + conv_dim(m) + m["mamba_num_heads"])
            + mamba_inner(m) * h)


def attn_params(m: dict) -> int:
    h, d = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * d
            + 2 * h * m["num_key_value_heads"] * d)


def expert_params(m: dict) -> int:
    """One routed expert: up and down."""
    return 2 * m["hidden_size"] * m["moe_intermediate_size"]


def expert_fixed_params(m: dict) -> int:
    """What an expert layer multiplies whatever was routed: the router and
    the shared expert."""
    h = m["hidden_size"]
    return (h * m["n_routed_experts"]
            + 2 * h * m["moe_shared_expert_intermediate_size"])


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def held_share(m: dict) -> float:
    """The share of the routed experts this chip holds."""
    return m["experts_held"][1] / m["n_routed_experts"]


def active_params_token(m: dict) -> float:
    """Matmul parameters one token multiplies by HERE, over all layers,
    without the head: the mixers, the routers, the shared experts and, of
    its ``num_experts_per_tok`` routed experts, the share that falls on the
    held range on average (3 of 6 at 64 of 128)."""
    return (layers(m, "M") * mamba_params(m) + layers(m, "*") * attn_params(m)
            + layers(m, "E") * (expert_fixed_params(m)
                                + m["num_experts_per_tok"] * held_share(m)
                                * expert_params(m)))


def ssd_chunk_flops_token_layer(m: dict) -> float:
    """One REAL token through one layer's chunked form at ``chunk_size`` L:
    the causal half of ``C B^T`` (2 N a pair a group) and of the masked
    product against ``x`` (2 P a pair a head), on average (L + 1) / 2 pairs
    a token, plus the carried state read through ``C`` and the token's
    addend to the state at the chunk's end (2 P N a head each)."""
    pairs = (m["chunk_size"] + 1) / 2.0
    h, p, n = (m["mamba_num_heads"], m["mamba_head_dim"],
               m["ssm_state_size"])
    return (2.0 * pairs * n * m["n_groups"] + 2.0 * pairs * p * h
            + 4.0 * h * p * n)


def ssd_step_flops_token_layer(m: dict) -> float:
    """One token through one layer's recurrence: the update ``x B^T`` and
    the read ``S C`` of every head."""
    return 4.0 * m["mamba_num_heads"] * m["mamba_head_dim"] \
        * m["ssm_state_size"]


def attn_flops_span(m: dict, start: int, stop: int) -> float:
    """Causal attention for the tokens at positions ``start .. stop-1``
    (position p attends to p + 1 keys), the attention layers."""
    n = stop - start
    keys = n * start + n * (n + 1) / 2.0
    return (4.0 * m["num_attention_heads"] * m["head_dim"] * keys
            * layers(m, "*"))


def prefill_flops(m: dict, start: int, stop: int) -> float:
    return ((2.0 * active_params_token(m)
             + layers(m, "M") * ssd_chunk_flops_token_layer(m))
            * (stop - start) + attn_flops_span(m, start, stop))


def decode_flops(m: dict, start: int, stop: int) -> float:
    """Forward that produces the tokens at positions ``start .. stop-1``."""
    n = stop - start
    return ((2.0 * (active_params_token(m) + head_params(m))
             + layers(m, "M") * ssd_step_flops_token_layer(m)) * n
            + attn_flops_span(m, start - 1, stop - 1))


def state_bytes_slot_layer(m: dict) -> int:
    """``S`` of every head of one Mamba-2 layer."""
    return (m["mamba_num_heads"] * m["mamba_head_dim"] * m["ssm_state_size"]
            * STATE_ITEMSIZE)


def conv_bytes_slot_layer(m: dict) -> int:
    """The convolution's carried K - 1 inputs of one Mamba-2 layer."""
    return (m["conv_kernel"] - 1) * conv_dim(m) * STATE_ITEMSIZE


def expert_up_stored(m: dict) -> int:
    """Columns of a held expert's first matrix as the program stores it: the
    width rounded up to a multiple of 128 lanes (1,856 -> 1,920). The grouped
    product reads and multiplies the padding with the rest."""
    return -(-m["moe_intermediate_size"] // 128) * 128


def expert_bytes(m: dict, itemsize: int = 2) -> int:
    """What reading one held expert's two matrices moves, as they are
    stored (20.30 MB; the model's own 2 h f parameters are 19.96 MB)."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return (h * expert_up_stored(m) + f * h) * itemsize


def fixed_bytes_step(m: dict, itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever was routed and whoever is live:
    every matmul weight outside the routed experts and the head."""
    return (layers(m, "M") * mamba_params(m) + layers(m, "*") * attn_params(m)
            + layers(m, "E") * expert_fixed_params(m)
            + head_params(m)) * itemsize


def decode_step_min_s(m: dict, experts_hit_layer: float, live_rows: float,
                      live_tokens: float, kv_bytes_token: float,
                      peak: dict) -> float:
    """The least time of one decode step: the fixed weights, the routed
    experts that some live token chose (a layer, from the program's
    counters), every live row's state and carried inputs read and written
    once a Mamba-2 layer, and the live keys and values, from HBM."""
    byts = (fixed_bytes_step(m)
            + layers(m, "E") * experts_hit_layer * expert_bytes(m)
            + live_rows * layers(m, "M") * 2 * (state_bytes_slot_layer(m)
                                                + conv_bytes_slot_layer(m))
            + kv_bytes_token * live_tokens)
    return byts / peak["hbm_bytes_per_s"]


def ssd_decode_min_s(m: dict, live_rows: float, peak: dict) -> float:
    """The least time of ONE layer's decode kernel call: bound by bytes,
    every live row's state read and written once, the convolution's outputs
    (x, B, C in bfloat16) in and y (float32) out."""
    row = (2 * state_bytes_slot_layer(m) + conv_dim(m) * 2
           + mamba_inner(m) * 4)
    return live_rows * row / peak["hbm_bytes_per_s"]


def ssd_chunk_min_s(m: dict, real_tokens: float, peak: dict) -> float:
    """The least time of ONE layer's share of a prefill's chunked form over
    ``real_tokens`` real tokens: bound by FLOPs."""
    return real_tokens * ssd_chunk_flops_token_layer(m) / peak["bf16_flops"]


def moe_gmm_min_s(m: dict, experts_hit: float, assignments: float,
                  rows: int, peak: dict) -> float:
    """The least time of ONE expert layer's two grouped products (up, then
    down) a decode step: ``experts_hit`` experts' matrices read once, the
    ``rows`` sorted rows in and out, against the operations of the
    ``assignments`` real rows."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    byts = (experts_hit * expert_bytes(m)
            + rows * (h + expert_up_stored(m) + f + h) * 2)
    flops = 2.0 * assignments * expert_params(m)
    return max(byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])
