"""Training-loop fault tolerance: numerical-health watchdog, crash-proof
DataLoader workers, auto-resume fit().

All faults are injected deterministically via FLAGS_fault_injection
(core/resilience.py) at the three training-robustness sites —
``health.nan_grad`` (poisons one gradient), ``dataloader.worker_crash``
(parent SIGKILLs a live worker process), ``fit.preempt`` (simulated
preemption at a batch boundary) — so these tests exercise the REAL
recovery paths: skip-step-and-shrink-scale, worker respawn + work
re-queue, and snapshot/restore.
"""
import os
import signal
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core import health, resilience
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.health import HealthMonitor, NonFiniteGradError
from paddle_tpu.core.resilience import InjectedFault
from paddle_tpu.hapi import Callback, Model
from paddle_tpu.io import (
    DataLoader,
    DataLoaderTimeoutError,
    DataLoaderWorkerError,
)
from paddle_tpu.io.dataset import Dataset


@pytest.fixture(autouse=True)
def _clean_state():
    resilience.reset_faults()
    resilience.reset_counters()
    health.reset_health()
    yield
    set_flags({"FLAGS_nonfinite_grad_policy": "off"})
    resilience.reset_faults()
    resilience.reset_counters()
    health.reset_health()


# ---------------------------------------------------------------- fixtures


class Squares(Dataset):
    def __init__(self, n=24):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        return np.float32(i)


class Corrupt(Dataset):
    """Every 5th sample raises (decode error analog)."""

    def __len__(self):
        return 12

    def __getitem__(self, i):
        if i % 5 == 0:
            raise ValueError(f"bad sample {i}")
        return np.float32(i)


class Regression(Dataset):
    def __init__(self, n=16):
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, 4).astype(np.float32)
        self.y = (self.x @ rng.randn(4, 1)).astype(np.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _build_model(lr=0.05):
    paddle.seed(7)
    net = nn.Linear(4, 1)
    m = Model(net)
    m.prepare(
        optimizer=paddle.optimizer.SGD(lr, parameters=net.parameters()),
        loss=lambda out, y: ((out - y) ** 2).mean())
    return m


def _weights(model):
    return np.asarray(model.network.weight._value).copy()


# --------------------------------------------- DataLoader fault tolerance


def test_worker_crash_is_respawned_and_epoch_completes():
    # ONE worker, so the epoch cannot end before the parent has seen the
    # death: the crash order sits in the task queue behind the first two
    # batches (prefetch_factor 2), and the other four exist only if the
    # worker is respawned. With a sibling alive the epoch could finish on
    # the sibling while the ordered worker was still exiting, and the
    # respawn count read 0 (the driver's runs, every one since the seed).
    set_flags({"FLAGS_fault_injection": "dataloader.worker_crash:1"})
    dl = DataLoader(Squares(24), batch_size=4, num_workers=1,
                    use_process_workers=True)
    vals = sorted(np.concatenate(
        [np.asarray(b._value) for b in dl]).tolist())
    # no batch lost to the killed worker: what was submitted and not
    # delivered was re-queued (and what then ran twice, deduped)
    assert vals == [float(i) for i in range(24)]
    assert resilience.get_counter("dataloader.worker_respawns") == 1
    assert resilience.get_counter("fault_injected:dataloader.worker_crash") == 1


def test_worker_crash_respawn_budget_exhaustion_raises_not_hangs():
    # a crash is ordered before every batch, and batch j >= 4 is queued
    # behind j - 3 of those orders: the 2 + 2 lives the budget allows
    # cannot get past batch 6, so an epoch of 12 batches cannot end without
    # the third death being seen. (At 6 batches the last one sat behind two
    # orders only: the epoch finished first and nothing was raised.)
    set_flags({"FLAGS_fault_injection": "dataloader.worker_crash:*"})
    dl = DataLoader(Squares(48), batch_size=4, num_workers=2,
                    use_process_workers=True, worker_respawn_limit=2)
    with pytest.raises(DataLoaderWorkerError) as ei:
        list(dl)
    assert ei.value.worker_id is not None  # names the dead worker
    assert "respawn budget" in str(ei.value)
    assert resilience.get_counter("dataloader.worker_respawns") == 2


class _SlowSample(Dataset):
    def __len__(self):
        return 4

    def __getitem__(self, i):
        if i == 2:
            time.sleep(30)
        return np.float32(i)


def test_timeout_is_honored_on_thread_workers():
    dl = DataLoader(_SlowSample(), batch_size=1, num_workers=1, timeout=0.3)
    t0 = time.monotonic()
    with pytest.raises(DataLoaderTimeoutError, match="timeout=0.3"):
        list(dl)
    assert time.monotonic() - t0 < 10  # raised, not hung


def test_timeout_is_honored_on_process_workers():
    dl = DataLoader(_SlowSample(), batch_size=1, num_workers=1, timeout=0.3,
                    use_process_workers=True)
    with pytest.raises(DataLoaderTimeoutError):
        list(dl)


def test_timeout_zero_means_wait_forever_still_works():
    dl = DataLoader(Squares(8), batch_size=2, num_workers=2, timeout=0)
    assert len(list(dl)) == 4


@pytest.mark.parametrize("workers", [
    dict(num_workers=0),
    dict(num_workers=2),
    dict(num_workers=2, use_process_workers=True),
])
def test_skip_corrupt_samples_counts_and_continues(workers):
    dl = DataLoader(Corrupt(), batch_size=4, skip_corrupt_samples=True,
                    **workers)
    n = sum(int(b.shape[0]) for b in dl)
    assert n == 9  # 12 samples, 3 corrupt (0, 5, 10)
    assert resilience.get_counter("dataloader.skipped_samples") == 3


def test_corrupt_sample_without_skip_still_fails_fast():
    dl = DataLoader(Corrupt(), batch_size=4, num_workers=0)
    with pytest.raises(ValueError, match="bad sample 0"):
        list(dl)


# -------------------------------------------- numerical-health watchdog


def test_injected_nan_grad_skips_step_shrinks_scale_bumps_counter():
    layer = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=layer.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=1024,
                                   decr_every_n_nan_or_inf=1)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    w0 = np.asarray(layer.weight._value).copy()

    set_flags({"FLAGS_fault_injection": "health.nan_grad:1"})
    scaler.scale(layer(x).sum()).backward()
    scaler.step(opt)
    scaler.update()
    opt.clear_grad()
    # step skipped: no weight corruption from the NaN gradient
    np.testing.assert_array_equal(w0, np.asarray(layer.weight._value))
    assert scaler.get_loss_scaling() == 512.0  # shrunk
    assert resilience.get_counter("health.nonfinite_grad") == 1
    assert resilience.get_counter("health.skipped_steps") == 1

    # next (finite) step applies normally at the reduced scale
    scaler.scale(layer(x).sum()).backward()
    scaler.step(opt)
    scaler.update()
    opt.clear_grad()
    assert not np.array_equal(w0, np.asarray(layer.weight._value))


def test_optimizer_policy_skip_preserves_weights_and_step_count():
    set_flags({"FLAGS_nonfinite_grad_policy": "skip"})
    layer = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=layer.parameters())
    layer(paddle.to_tensor(np.ones((2, 4), np.float32))).sum().backward()
    gv = layer.weight._grad._value
    layer.weight._grad._value = np.full(np.shape(gv), np.nan,
                                        np.asarray(gv).dtype)
    w0 = np.asarray(layer.weight._value).copy()
    steps0 = opt._step_count
    opt.step()
    np.testing.assert_array_equal(w0, np.asarray(layer.weight._value))
    assert opt._step_count == steps0  # skipped like a GradScaler skip
    assert resilience.get_counter("health.skipped_steps") == 1


def test_optimizer_policy_raise_names_the_parameter():
    set_flags({"FLAGS_nonfinite_grad_policy": "raise",
               "FLAGS_fault_injection": "health.nan_grad:1"})
    layer = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=layer.parameters())
    layer(paddle.to_tensor(np.ones((2, 4), np.float32))).sum().backward()
    with pytest.raises(NonFiniteGradError) as ei:
        opt.step()
    assert ei.value.param_name is not None


def test_optimizer_policy_off_never_syncs_or_checks():
    layer = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=layer.parameters())
    layer(paddle.to_tensor(np.ones((2, 4), np.float32))).sum().backward()
    gv = layer.weight._grad._value
    layer.weight._grad._value = np.full(np.shape(gv), np.nan,
                                        np.asarray(gv).dtype)
    opt.step()  # default: no detection, NaN propagates (legacy behavior)
    assert resilience.get_counter("health.nonfinite_grad") == 0


def test_optimizer_policy_skip_vets_sparse_grads_before_apply():
    # row-sparse grads are scatter-added straight into the weights —
    # the watchdog must run BEFORE that, not after
    from paddle_tpu.core.selected_rows import SelectedRows

    set_flags({"FLAGS_nonfinite_grad_policy": "skip"})
    emb = paddle.Parameter(np.ones((6, 3), np.float32))
    opt = paddle.optimizer.SGD(0.1, parameters=[emb])
    emb._grad = SelectedRows(rows=np.array([1, 4]),
                             value=np.full((2, 3), np.nan, np.float32),
                             height=6)
    w0 = np.asarray(emb._value).copy()
    opt.step()
    np.testing.assert_array_equal(w0, np.asarray(emb._value))
    assert resilience.get_counter("health.skipped_steps") == 1


def test_scaler_managed_step_skips_not_raises_under_raise_policy():
    # GradScaler.step vets grads in unscale_ and marks them; the
    # optimizer watchdog must not re-check (no double device sync) and
    # the scaler's skip semantics win over the raise policy
    set_flags({"FLAGS_nonfinite_grad_policy": "raise",
               "FLAGS_fault_injection": "health.nan_grad:1"})
    layer = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=layer.parameters())
    scaler = paddle.amp.GradScaler(init_loss_scaling=64)
    w0 = np.asarray(layer.weight._value).copy()
    scaler.scale(layer(paddle.to_tensor(
        np.ones((2, 4), np.float32))).sum()).backward()
    scaler.step(opt)  # no NonFiniteGradError: skip + shrink instead
    scaler.update()
    np.testing.assert_array_equal(w0, np.asarray(layer.weight._value))
    assert resilience.get_counter("health.skipped_steps") == 1


def test_loss_spike_ema_detector():
    mon = HealthMonitor(spike_factor=10.0, spike_ema=0.5, spike_warmup=3)
    for _ in range(5):
        assert mon.record_loss(1.0)
    assert resilience.get_counter("health.loss_spike") == 0
    mon.record_loss(100.0)  # > 10 * EMA(≈1)
    assert resilience.get_counter("health.loss_spike") == 1
    assert not mon.record_loss(float("nan"))
    assert resilience.get_counter("health.nonfinite_loss") == 1


def test_grad_scaler_state_dict_roundtrips_dynamic_bookkeeping():
    s = paddle.amp.GradScaler(init_loss_scaling=2.0 ** 10, incr_ratio=3.0,
                              decr_ratio=0.25, incr_every_n_steps=7,
                              decr_every_n_nan_or_inf=4)
    s._good_steps, s._bad_steps = 5, 2
    s._scale = 123.0
    state = s.state_dict()
    fresh = paddle.amp.GradScaler()  # defaults everywhere
    fresh.load_state_dict(state)
    assert fresh.get_loss_scaling() == 123.0
    assert fresh.get_growth_tracker() == 5
    assert fresh._bad_steps == 2
    assert fresh._incr_ratio == 3.0 and fresh._decr_ratio == 0.25
    assert fresh._incr_every_n_steps == 7
    assert fresh._decr_every_n_nan_or_inf == 4


def test_check_numerics_debug_modes_and_counter():
    from paddle_tpu.amp.debugging import DebugMode, check_numerics

    bad = paddle.to_tensor(np.array([1.0, np.nan, np.inf], np.float32))
    with pytest.raises(FloatingPointError, match=r"op_type=mul.*var_name=x"):
        check_numerics(bad, op_type="mul", var_name="x")
    assert resilience.get_counter("health.check_numerics") == 1
    # CHECK_NAN_INF: logged + counted, not raised
    check_numerics(bad, op_type="mul", var_name="x",
                   debug_mode=DebugMode.CHECK_NAN_INF)
    assert resilience.get_counter("health.check_numerics") == 2
    check_numerics(paddle.to_tensor(np.ones(3, np.float32)))  # clean: no-op
    assert resilience.get_counter("health.check_numerics") == 2


def test_tensor_checker_feeds_health_counters():
    from paddle_tpu.amp.debugging import (
        DebugMode,
        TensorCheckerConfig,
        disable_tensor_checker,
        enable_tensor_checker,
    )

    x = paddle.to_tensor(np.array([0.0], np.float32))
    enable_tensor_checker(TensorCheckerConfig(
        debug_mode=DebugMode.CHECK_NAN_INF))
    try:
        _ = x / x  # 0/0 -> NaN, logged not raised in CHECK_NAN_INF mode
        assert resilience.get_counter("health.tensor_checker_nan_inf") >= 1
    finally:
        disable_tensor_checker()
    with pytest.raises(FloatingPointError):  # default mode aborts
        enable_tensor_checker()
        try:
            _ = x / x
        finally:
            disable_tensor_checker()


# ------------------------------------------------------- auto-resume fit()


class _ArmPreemptAt(Callback):
    """Arm the fit.preempt fault site after N batches (so the preemption
    lands mid-run, not at step 0)."""

    def __init__(self, at):
        self.at = at
        self.n = 0

    def on_train_batch_end(self, step, logs=None):
        self.n += 1
        if self.n == self.at:
            set_flags({"FLAGS_fault_injection": "fit.preempt:1"})


def test_fit_preempted_mid_epoch_resumes_bit_exact(tmp_path):
    # uninterrupted reference run (shuffle exercises the epoch-start RNG
    # replay on resume)
    ref = _build_model()
    ref.fit(Regression(), batch_size=4, epochs=3, shuffle=True, verbose=0)
    w_ref = _weights(ref)

    ckpt = str(tmp_path / "ckpt")
    victim = _build_model()
    with pytest.raises(InjectedFault):
        victim.fit(Regression(), batch_size=4, epochs=3, shuffle=True,
                   verbose=0, checkpoint_dir=ckpt, checkpoint_freq=1,
                   callbacks=[_ArmPreemptAt(6)])  # dies mid-epoch 2
    resilience.reset_faults()
    # a snapshot was written by the preemption path
    from paddle_tpu.distributed.checkpoint import latest_complete_snapshot

    assert latest_complete_snapshot(ckpt) is not None

    survivor = _build_model()  # fresh process analog (same seed init)
    survivor.fit(Regression(), batch_size=4, epochs=3, shuffle=True,
                 verbose=0, resume=True, checkpoint_dir=ckpt,
                 checkpoint_freq=1)
    np.testing.assert_array_equal(w_ref, _weights(survivor))


def test_fit_resume_restores_optimizer_and_scaler_state(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    paddle.seed(11)
    net = nn.Linear(4, 1)
    m = Model(net)
    scaler = paddle.amp.GradScaler(init_loss_scaling=256.0,
                                   incr_every_n_steps=2)
    m.prepare(
        optimizer=paddle.optimizer.Adam(
            0.01, parameters=net.parameters()),
        loss=lambda out, y: ((out - y) ** 2).mean(), scaler=scaler)
    with pytest.raises(InjectedFault):
        m.fit(Regression(), batch_size=4, epochs=2, shuffle=False,
              verbose=0, checkpoint_dir=ckpt, checkpoint_freq=1,
              callbacks=[_ArmPreemptAt(5)])
    resilience.reset_faults()
    scale_at_kill = scaler.get_loss_scaling()
    growth_at_kill = scaler.get_growth_tracker()
    opt_steps_at_kill = m._optimizer._step_count
    moment = {k: np.asarray(v).copy()
              for k, v in m._optimizer._accumulators.items()}

    paddle.seed(11)
    net2 = nn.Linear(4, 1)
    m2 = Model(net2)
    scaler2 = paddle.amp.GradScaler()  # defaults — restore must fix them
    m2.prepare(
        optimizer=paddle.optimizer.Adam(
            0.01, parameters=net2.parameters()),
        loss=lambda out, y: ((out - y) ** 2).mean(), scaler=scaler2)
    restored = m2._restore_training_snapshot(ckpt)
    assert restored is not None
    assert scaler2.get_loss_scaling() == scale_at_kill
    assert scaler2.get_growth_tracker() == growth_at_kill
    assert m2._optimizer._step_count == opt_steps_at_kill
    for k, v in moment.items():
        np.testing.assert_array_equal(v,
                                      np.asarray(m2._optimizer._accumulators[k]),
                                      err_msg=k)


def test_fit_sigterm_checkpoints_once_then_exits_and_resumes(tmp_path):
    ckpt = str(tmp_path / "ckpt")

    class KillAt(Callback):
        def __init__(self, at):
            self.at = at
            self.n = 0

        def on_train_batch_end(self, step, logs=None):
            self.n += 1
            if self.n == self.at:
                os.kill(os.getpid(), signal.SIGTERM)

    victim = _build_model()
    with pytest.raises(SystemExit) as ei:
        victim.fit(Regression(), batch_size=4, epochs=2, shuffle=False,
                   verbose=0, checkpoint_dir=ckpt, checkpoint_freq=100,
                   callbacks=[KillAt(3)])
    assert ei.value.code == 143  # 128 + SIGTERM
    assert any(d.startswith("step_") for d in os.listdir(ckpt))

    survivor = _build_model()
    survivor.fit(Regression(), batch_size=4, epochs=2, shuffle=False,
                 verbose=0, resume=True, checkpoint_dir=ckpt)
    ref = _build_model()
    ref.fit(Regression(), batch_size=4, epochs=2, shuffle=False, verbose=0)
    np.testing.assert_array_equal(_weights(ref), _weights(survivor))


def test_iter_from_skips_without_loading_and_matches_rng():
    loads = []

    class Tracking(Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, i):
            loads.append(i)
            return np.float32(i)

    paddle.seed(123)
    dl = DataLoader(Tracking(), batch_size=4, shuffle=True)
    full = [np.asarray(b._value) for b in dl]
    paddle.seed(123)
    loads.clear()
    tail = [np.asarray(b._value) for b in dl.iter_from(2)]
    assert len(loads) == 8  # skipped batches never hit dataset[i]
    np.testing.assert_array_equal(np.concatenate(full[2:]),
                                  np.concatenate(tail))
    with pytest.raises(ValueError, match="data pipeline changed"):
        dl.iter_from(99)


def test_fit_resume_rejects_changed_data_pipeline(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    m = _build_model()
    with pytest.raises(InjectedFault):
        m.fit(Regression(), batch_size=4, epochs=2, shuffle=False,
              verbose=0, checkpoint_dir=ckpt, checkpoint_freq=1,
              callbacks=[_ArmPreemptAt(2)])
    resilience.reset_faults()
    m2 = _build_model()
    with pytest.raises(ValueError, match="data pipeline changed"):
        # batch_size 16 -> the epoch now has 1 batch, snapshot says 2
        m2.fit(Regression(), batch_size=16, epochs=2, shuffle=False,
               verbose=0, resume=True, checkpoint_dir=ckpt)


def test_fit_resume_without_snapshot_is_fresh_start(tmp_path):
    m = _build_model()
    hist = m.fit(Regression(), batch_size=4, epochs=1, shuffle=False,
                 verbose=0, resume=True,
                 checkpoint_dir=str(tmp_path / "empty"))
    assert len(hist) == 1


def test_fit_resume_requires_checkpoint_dir():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        _build_model().fit(Regression(), batch_size=4, epochs=1,
                           verbose=0, resume=True)


def test_fit_snapshots_pruned_to_keep(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    m = _build_model()
    m.fit(Regression(), batch_size=4, epochs=1, shuffle=False, verbose=0,
          checkpoint_dir=ckpt, checkpoint_freq=1, keep_checkpoints=2)
    snaps = [d for d in os.listdir(ckpt) if d.startswith("step_")]
    assert len(snaps) == 2  # pruned from 4 steps to the newest 2
