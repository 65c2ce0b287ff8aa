"""``chip_smoke.py`` rehearsed on the CPU: its serve, train and four-chip
phase functions at a tiny config over the virtual CPU devices of
``conftest.py`` (Pallas kernels interpreted), and ``main()`` refusing to
report success without a TPU. The real widths, the device gate and the
Mosaic-kernel requirements live in ``main()``/``run()`` and only ever pass
on the chip."""
import json

import numpy as np
import pytest

import chip_smoke
import paddle_tpu.distributed as dist
from paddle_tpu.models import LlamaConfig


@pytest.fixture(autouse=True)
def _restore():
    yield
    dist.set_mesh(None)


def _cfg(**kw):
    base = dict(vocab_size=160, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                max_position_embeddings=256)
    base.update(kw)
    return LlamaConfig(**base)


def test_serve_phase_tiny():
    rep = chip_smoke.serve_phase(
        _cfg(), seed=0, max_slots=2, max_len=128, page_size=16,
        prompt_buckets=(16,), pool_pages=12,
        prompt_lens=(5, 40, 16, 9, 36), shared_prefix=24,
        max_new=(6, 8, 5, 7, 6), segment=4, plain_len=64)
    assert rep["post_warmup_compiles_serving"] == 0
    assert rep["post_warmup_compiles_any"] == 0
    assert rep["prefix_tokens_saved"] > 0
    assert rep["warmup_programs"] >= 8
    assert rep["streams_vs_plain"]["tokens_checked"] > 0
    assert rep["cache_vs_plain"]["max_abs_logit_diff"] \
        <= rep["cache_vs_plain"]["tolerance"]
    # interpreted kernels leave no Mosaic call: run() would refuse this
    assert rep["mosaic_in_segment"] is False
    # a CPU trace has no device plane: Profiler.summary() says so and the
    # report carries no split (run() requires one on the chip); that the
    # program table outlived both engines is checked inside the phase
    assert rep["segment_time_split"] is None
    assert rep["kv_pool_bytes"] > 0 and rep["weight_bytes"] > 0


def test_train_phase_tiny():
    rep = chip_smoke.train_phase(
        _cfg(num_hidden_layers=1, use_recompute=True), seed=0, batch=2,
        seq=128, steps=3, lr=1e-2)
    assert len(rep["losses"]) == 3
    assert rep["losses"][-1] < rep["losses"][0]
    assert rep["mosaic_in_step"] is False
    # a CPU trace has no device plane: Profiler.summary() says so and the
    # report carries no split (run() requires one on the chip)
    assert rep["step_time_split"] is None


def test_train_phase_refuses_the_sdpa_fallback():
    """A sequence the flash kernel cannot serve makes the phase fail
    instead of training on the XLA composition."""
    with pytest.raises(UserWarning, match="falling back to the XLA sdpa"):
        chip_smoke.train_phase(_cfg(), seed=0, batch=2, seq=24, steps=1,
                               lr=1e-2)


def test_multichip_phase_tiny():
    rep = chip_smoke.multichip_phase(
        _cfg(num_hidden_layers=1), seed=0, chips=4, max_slots=1, max_len=64,
        page_size=16, prompt_buckets=(16,), prompt_lens=(5, 30),
        max_new=(6, 6), segment=3, batch=2, seq=128, steps=2, lr=1e-2)
    assert rep["chips"] == 4
    assert rep["serve_collectives"] and rep["train_collectives"]
    assert sorted(rep["serve_kv_shard_bytes"]) == [0, 1, 2, 3]
    assert sorted(rep["train_param_shard_bytes"]) == [0, 1, 2, 3]
    assert len(set(rep["serve_kv_shard_bytes"].values())) == 1
    np.testing.assert_allclose(rep["train_losses_mesh"],
                               rep["train_losses_single"], rtol=2 ** -6)


@pytest.mark.parametrize("gap,accepted", [(0.01, True), (0.5, False)],
                         ids=["near_tie", "real_difference"])
def test_streams_may_differ_only_at_a_near_tie(gap, accepted):
    """Two evaluation orders of one bf16 model may pick different tokens
    where the plain forward's logits all but tie — and nowhere else."""
    class Plain:   # token 3 wins every position, token 5 trails by `gap`
        def __call__(self, ids):
            logits = np.zeros(tuple(ids.shape) + (8,), np.float32)
            logits[..., 3], logits[..., 5] = 1.0, 1.0 - gap
            return type("T", (), {"_value": logits})

    prompts = [np.array([1, 2], np.int32)]
    a, b = {0: np.array([3, 3, 3])}, {0: np.array([3, 5, 3])}
    if accepted:
        rep = chip_smoke.check_streams_agree(a, b, "a vs b", Plain(),
                                             prompts)
        assert rep["identical_requests"] == 0
        assert rep["first_difference_at_near_tie"] == {0: 1}
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match="near-tie"):
            chip_smoke.check_streams_agree(a, b, "a vs b", Plain(), prompts)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_main_without_a_tpu_fails(capsys, argv):
    assert chip_smoke.main(argv) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    verdict = json.loads(last)
    assert verdict["ok"] is False
    assert "no TPU" in verdict["reason"]
    assert verdict["device"]["platform"] == "cpu"


def test_a_failed_phase_fails_main(capsys, monkeypatch):
    """With the device gate passed, a phase that raises still ends in
    ``"ok": false`` and a non-zero exit — nothing carries on."""
    class FakeTpu:
        platform, device_kind, id = "tpu", "fake", 0

    def boom(chips, device):
        raise chip_smoke.SmokeFailure("forced failure of one phase")

    monkeypatch.setattr(chip_smoke.jax, "devices", lambda: [FakeTpu()])
    monkeypatch.setattr(chip_smoke, "run", boom)
    assert chip_smoke.main([]) == 1
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert verdict["ok"] is False
    assert "forced failure" in verdict["reason"]
