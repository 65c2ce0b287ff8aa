import json
import os

import pytest

from benchmark import costs, weights

HERE = os.path.dirname(os.path.abspath(__file__))


def model(name):
    with open(os.path.join(os.path.dirname(HERE), "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_d16_counts_by_hand():
    c = model("mistral-7b-v0.3-d16")
    m = c["model"]
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336      # q,o + k,v + mlp
    assert costs.layer_params(m) == layer == 218103808
    assert costs.matmul_params(m) == 16 * layer + 4096 * 32768
    assert weights.count_params(m) == c["params"] == 16 * (layer + 2 * 4096) + 2 * 4096 * 32768 + 4096
    assert costs.kv_bytes_token(m) == 2 * 8 * 128 * 2 * 16 == 65536
    assert costs.weight_bytes_step(m) == 2 * costs.matmul_params(m)
    # one decode step at 10,000 live tokens: weights + KV over 819 GB/s
    peak = costs.peaks("TPU v5 lite")
    assert costs.decode_step_min_s(m, 10000, peak) == pytest.approx(
        (2 * 3623878656 + 65536 * 10000) / 819e9)


def test_internlm2_counts_by_hand():
    d12 = model("internlm2-1.8b-d12")
    full = dict(d12, params=1889110016,
                model=dict(d12["model"], num_hidden_layers=24))   # published depth
    for c, layers in ((d12, 12), (full, 24)):
        m = c["model"]
        layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
        assert costs.layer_params(m) == layer == 62914560
        n = layers * layer + 2048 * 92544
        assert costs.matmul_params(m) == n
        assert weights.count_params(m) == c["params"]
        # 6 N per token and 3 x causal attention (4 h d S(S+1)/2 per layer)
        attn = 3 * 4 * 16 * 128 * (4096 * 4097 / 2) * layers
        assert costs.train_flops_tokens(m, 2, 4096) == pytest.approx(
            2 * (6 * n * 4096 + attn))


def test_attention_spans_add_up():
    m = model("mistral-7b-v0.3-d16")["model"]
    whole = costs.attn_flops_span(m, 0, 300)
    assert whole == pytest.approx(costs.attn_flops_span(m, 0, 128)
                                  + costs.attn_flops_span(m, 128, 300))
    assert costs.attn_flops_span(m, 0, 1) == costs.attn_flops_token(m, 1)
    # decoding token at position p attends p keys (the context before it) + itself
    assert costs.decode_flops(m, 10, 11) == pytest.approx(
        2 * costs.matmul_params(m) + costs.attn_flops_token(m, 10))


def test_kernel_floors():
    m = model("internlm2-1.8b-d12")["model"]
    peak = costs.peaks("TPU v5 lite")
    fwd = costs.flash_min_s(m, 2, 4096, peak)
    assert fwd == pytest.approx(2 * 4 * 16 * 128 * 4096 * 4097 / 2 / 197e12)
    assert costs.flash_min_s(m, 2, 4096, peak, backward=True) == pytest.approx(2.5 * fwd)
    mm = model("mistral-7b-v0.3-d16")["model"]
    # paged decode attention is bound by its bytes: K and V of the live tokens
    assert costs.paged_attn_min_s(mm, 8000, 32, peak) == pytest.approx(
        (8000 * 2 * 8 * 128 * 2 + 2 * 32 * 32 * 128 * 2) / 819e9)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    with pytest.raises(KeyError):
        costs.peaks("_source")
    assert costs.peaks("TPU v5 lite")["bf16_flops"] == 197e12
