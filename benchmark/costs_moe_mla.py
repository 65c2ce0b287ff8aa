"""Operations and bytes of the sparse-expert / latent-attention model, from
a configuration's sizes and the counts the program made (``costs.py``'s rules:
a multiply-add is 2, the embedding lookup is not a matmul, what the algorithm
needs and not what an implementation spends).

``m`` is the configuration's model section with ``experts_held`` beside it
(``program_moe_mla.model_section``).
"""
from __future__ import annotations


def attn_params(m: dict) -> int:
    """Matmul parameters of one layer's latent attention."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * (nope + rope)
            + h * (m["kv_lora_rank"] + rope)
            + m["kv_lora_rank"] * heads * (nope + vd) + heads * vd * h)


def expert_params(m: dict) -> int:
    """One routed (or the shared) expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def sparse_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def active_params_token(m: dict) -> int:
    """Matmul parameters one token multiplies by, over all layers, without
    the head: attention, the dense layers' SwiGLU, and in a sparse layer the
    router, the chosen routed experts and the shared ones."""
    dense = m["first_k_dense_replace"]
    sparse = (m["hidden_size"] * m["n_routed_experts"]
              + (m["num_experts_per_tok"] + m["n_shared_experts"])
              * expert_params(m))
    return (m["num_hidden_layers"] * attn_params(m)
            + dense * 3 * m["hidden_size"] * m["intermediate_size"]
            + sparse_layers(m) * sparse)


def head_params(m: dict) -> int:
    return m["hidden_size"] * m["vocab_size"]


def attn_flops_span(m: dict, start: int, stop: int) -> float:
    """Causal attention in the PLAIN form for the tokens at positions
    ``start .. stop-1`` (position p attends to p + 1 keys), all layers:
    scores over the nope + rope head size, values over the v head size."""
    n = stop - start
    keys = n * start + n * (n + 1) / 2.0
    per_key = 2.0 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])
    return per_key * keys * m["num_hidden_layers"]


def prefill_flops(m: dict, start: int, stop: int) -> float:
    return (2.0 * active_params_token(m) * (stop - start)
            + attn_flops_span(m, start, stop))


def decode_flops(m: dict, start: int, stop: int) -> float:
    """Forward that produces the tokens at positions ``start .. stop-1``."""
    n = stop - start
    return (2.0 * (active_params_token(m) + head_params(m)) * n
            + attn_flops_span(m, start - 1, stop - 1))


def expert_bytes(m: dict, itemsize: int = 2) -> int:
    """What reading one routed expert's three matrices costs."""
    return expert_params(m) * itemsize


def fixed_bytes_step(m: dict, itemsize: int = 2) -> int:
    """Bytes a decode step reads whatever was routed: every matmul weight
    outside the routed experts (attention, dense SwiGLU, routers, shared
    experts) and the head; the embedded rows are noise beside them."""
    return (active_params_token(m) + head_params(m)
            - sparse_layers(m) * m["num_experts_per_tok"] * expert_params(m)
            ) * itemsize


def decode_step_min_s(m: dict, experts_hit_layer: float, live_tokens: float,
                      kv_bytes_token: float, peak: dict) -> float:
    """The least time of one decode step: the fixed weights, the routed
    experts that some token chose (``experts_hit_layer`` a sparse layer,
    from the program's counters) and the live latent cache, from HBM."""
    byts = (fixed_bytes_step(m)
            + sparse_layers(m) * experts_hit_layer * expert_bytes(m)
            + kv_bytes_token * live_tokens)
    return byts / peak["hbm_bytes_per_s"]


def moe_gmm_min_s(m: dict, experts_hit: float, assignments: float,
                  rows: int, peak: dict) -> float:
    """The least time of ONE sparse layer's two grouped products (gate|up,
    then down) a decode step: ``experts_hit`` experts' matrices read once,
    the ``rows`` sorted rows in and out, against the operations of the
    ``assignments`` real rows."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    byts = (experts_hit * expert_bytes(m)
            + rows * (h + 2 * f + f + h) * 2)
    flops = 2.0 * assignments * expert_params(m)
    return max(byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])


def paged_mla_min_s(m: dict, live_tokens: float, slots: int,
                    peak: dict) -> float:
    """The least time of ONE layer's decode attention over the latent
    cache: every live token's latent and rope key read once, the queries in
    and the latent outputs out; scores over latent + rope, values over the
    latent, for every head."""
    c, r, heads = (m["kv_lora_rank"], m["qk_rope_head_dim"],
                   m["num_attention_heads"])
    byts = live_tokens * (c + r) * 2 + slots * heads * (2 * c + r) * 2
    flops = 2.0 * heads * (2 * c + r) * live_tokens
    return max(byts / peak["hbm_bytes_per_s"], flops / peak["bf16_flops"])
