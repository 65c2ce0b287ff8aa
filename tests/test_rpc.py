"""Hardened RPC transport (reference paddle.distributed.rpc) — ISSUE 7.

The robustness contract under the cross-process serving fleet:
at-least-once delivery with ack-after-execute, rid-idempotent dedup on
the callee (a resent request never re-executes), bounded store growth
(reply + inbox slot keys are GC'd), a worker pool so a slow call cannot
head-of-line-block a health probe, typed remote errors, and
retry-budgeted resends that drill through the deterministic fault sites
``rpc.send_drop`` / ``rpc.reply_drop`` / ``rpc.delay``.
"""
import operator
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.resilience import (
    CommTimeoutError,
    RetryPolicy,
    ServingUnavailable,
)
from paddle_tpu.distributed import rpc


@pytest.fixture(autouse=True)
def _clean_resilience():
    resilience.reset_faults()
    resilience.reset_counters()
    yield
    resilience.reset_faults()
    resilience.reset_counters()


@pytest.fixture
def rpc_env():
    store = rpc.init_rpc("worker0", rank=0, world_size=1)
    yield store
    rpc.shutdown()


# ------------------------------------------------------------- basics


def test_rpc_sync_scalar(rpc_env):
    assert rpc.rpc_sync("worker0", operator.add, args=(3, 4)) == 7


def test_rpc_tensor_payload(rpc_env):
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = rpc.rpc_sync("worker0", np.sum, args=(x,))
    assert out == 15.0
    y = rpc.rpc_sync("worker0", np.transpose, args=(x,))
    np.testing.assert_array_equal(y, x.T)


def test_rpc_async_futures(rpc_env):
    futs = [rpc.rpc_async("worker0", operator.mul, args=(i, i))
            for i in range(5)]
    assert [f.wait() for f in futs] == [0, 1, 4, 9, 16]


def test_worker_info(rpc_env):
    info = rpc.get_worker_info()
    assert info.name == "worker0" and info.rank == 0
    assert rpc.get_worker_info("worker0").rank == 0


def test_worker_info_unknown_name_honors_timeout(rpc_env):
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="ghost"):
        rpc.get_worker_info("ghost", timeout=0.2)
    # must not fall into the store's 900s rendezvous default
    assert time.monotonic() - t0 < 5.0


# ----------------------------------------------------- in-memory codec


def test_codec_round_trips_nested_payloads():
    from paddle_tpu.distributed.rpc import _decode, _encode

    x = np.arange(12, dtype=np.int32).reshape(3, 4)
    payload = {
        "rows": [[7, "ok", x, None], (1, 2.5)],
        17: {"nested": x.astype(np.float64)},   # non-string dict key
        "empty": np.zeros((0,), np.int32),
    }
    out = _decode(_encode(payload))
    assert out["rows"][1] == (1, 2.5)           # tuples survive
    np.testing.assert_array_equal(out["rows"][0][2], x)
    np.testing.assert_array_equal(out[17]["nested"], x.astype(np.float64))
    assert out[17]["nested"].dtype == np.float64
    assert out["empty"].size == 0
    assert out["rows"][0][3] is None


def test_codec_no_tempfile_and_no_dead_io_import():
    import pathlib

    src = pathlib.Path(rpc.__file__).read_text()
    assert "import tempfile" not in src  # in-memory encode only
    assert "_pyio" not in src            # the dead io alias is gone


# ----------------------------------------------------- typed remote errors


def test_remote_builtin_error_reraises_typed(rpc_env):
    with pytest.raises(ZeroDivisionError, match="division by zero") as ei:
        rpc.rpc_sync("worker0", operator.truediv, args=(1, 0))
    assert ei.value.remote_traceback  # remote frames ride along


def _raise_serving_unavailable():
    raise ServingUnavailable("replica gone (drill)")


def test_remote_resilience_error_reraises_typed(rpc_env):
    with pytest.raises(ServingUnavailable, match="replica gone"):
        rpc.rpc_sync("worker0", _raise_serving_unavailable)


class _ExoticError(Exception):
    pass


def _raise_exotic():
    raise _ExoticError("no such type caller-side")


def test_remote_unknown_error_wraps_as_rpc_remote_error(rpc_env):
    with pytest.raises(rpc.RpcRemoteError,
                       match="_ExoticError: no such type"):
        rpc.rpc_sync("worker0", _raise_exotic)


def _return_unserializable():
    return {1, 2, 3}  # a set does not survive the codec


def test_unserializable_result_errors_instead_of_hanging(rpc_env):
    """A result the codec cannot encode must come back as a typed error
    reply, not strand the caller until its overall timeout with the
    request poisoned at 'pending' and its inbox slot never acked."""
    t0 = time.monotonic()
    with pytest.raises(TypeError, match="not JSON serializable"):
        rpc.rpc_sync("worker0", _return_unserializable, timeout=30.0)
    assert time.monotonic() - t0 < 10.0  # the error reply, not the timeout
    # the slot was acked and the dispatcher still serves
    assert rpc.rpc_sync("worker0", operator.add, args=(2, 2),
                        timeout=10.0) == 4


# -------------------------------------------------- bounded store growth


def test_reply_and_inbox_keys_are_gcd(rpc_env):
    """Across N calls the per-call store keys (reply + inbox slot) must
    all be gone — only the two per-worker inbox counters persist."""
    store = rpc_env
    n = 12
    futs = [rpc.rpc_async("worker0", operator.add, args=(i, 1))
            for i in range(n)]
    ids = [f._id for f in futs]
    assert [f.wait(timeout=30) for f in futs] == list(range(1, n + 1))
    for req_id in ids:
        assert not store.check(f"rpc/reply/{req_id}")
    deadline = time.monotonic() + 10
    while (any(store.check(f"rpc/inbox/worker0/{s}") for s in range(n))
           and time.monotonic() < deadline):
        time.sleep(0.01)  # the post-execute ack is asynchronous
    for slot in range(n):
        assert not store.check(f"rpc/inbox/worker0/{slot}")
    assert int(store.add("rpc/inbox/worker0", 0)) == n
    assert int(store.add("rpc/inbox/worker0/claimed", 0)) == n


# -------------------------------------------------------- worker pool

_slow_gate = threading.Event()


def _slow_call():
    _slow_gate.wait(10.0)
    return "slow done"


def test_slow_call_does_not_block_concurrent_probe(rpc_env):
    """Head-of-line blocking drill: while one pool worker is stuck in a
    slow call, a health-probe-shaped fast call must still answer."""
    _slow_gate.clear()
    try:
        slow = rpc.rpc_async("worker0", _slow_call)
        t0 = time.monotonic()
        assert rpc.rpc_sync("worker0", operator.add, args=(1, 1),
                            timeout=5.0) == 2
        assert time.monotonic() - t0 < 5.0
        assert not slow.done()
    finally:
        _slow_gate.set()
    assert slow.wait(timeout=10) == "slow done"


def test_delay_fault_stalls_one_call_not_the_pool(rpc_env):
    set_flags({"FLAGS_fault_injection": "rpc.delay:1"})
    delayed = rpc.rpc_async("worker0", operator.add, args=(1, 2))
    time.sleep(0.02)  # let the delayed call claim its pool worker
    t0 = time.monotonic()
    assert rpc.rpc_sync("worker0", operator.add, args=(3, 4),
                        timeout=5.0) == 7
    overtake = time.monotonic() - t0
    assert delayed.wait(timeout=10) == 3
    assert overtake < rpc.DELAY_FAULT_S
    assert resilience.get_counter("rpc.delayed") == 1


# ---------------------------------------- retries, dedup, fault drills

_effects_lock = threading.Lock()
_effects: list = []


def _record_effect(tag):
    with _effects_lock:
        _effects.append(tag)
    return len(_effects)


def test_send_drop_recovered_by_resend_exactly_once(rpc_env):
    """The send vanishes on the wire: the resend budget re-posts it and
    the observable effect happens exactly once."""
    del _effects[:]
    set_flags({"FLAGS_fault_injection": "rpc.send_drop:1"})
    out = rpc.rpc_sync("worker0", _record_effect, args=("a",),
                       timeout=30.0, retry=3, resend_after=0.2)
    assert out == 1
    assert _effects == ["a"]
    assert resilience.get_counter("rpc.send_dropped") == 1
    assert resilience.get_counter("rpc.resend") >= 1


def test_reply_drop_resend_dedups_no_reexecution(rpc_env):
    """The reply vanishes AFTER the callee executed: the resend must hit
    the rid dedup cache — the cached reply is re-written, the side
    effect happens exactly once (exactly-once observable effects)."""
    del _effects[:]
    set_flags({"FLAGS_fault_injection": "rpc.reply_drop:1"})
    out = rpc.rpc_sync("worker0", _record_effect, args=("b",),
                       timeout=30.0, retry=4, resend_after=0.2)
    assert out == 1
    assert _effects == ["b"]
    assert resilience.get_counter("rpc.reply_dropped") == 1
    assert resilience.get_counter("rpc.redelivered") >= 1


def test_retry_accepts_retry_policy_budget(rpc_env):
    del _effects[:]
    set_flags({"FLAGS_fault_injection": "rpc.send_drop:1"})
    out = rpc.rpc_sync(
        "worker0", _record_effect, args=("c",), timeout=30.0,
        retry=RetryPolicy(max_attempts=3, base_delay=0.01),
        resend_after=0.2)
    assert out == 1 and _effects == ["c"]


def test_exhausted_retry_budget_names_the_peer(rpc_env):
    """Every send drops: the budget burns down and surfaces a
    CommTimeoutError naming src/dst and the request."""
    set_flags({"FLAGS_fault_injection": "rpc.send_drop:*"})
    with pytest.raises(CommTimeoutError) as ei:
        rpc.rpc_sync("worker0", operator.add, args=(1, 1),
                     timeout=1.5, retry=3, resend_after=0.2)
    msg = str(ei.value)
    assert "worker0" in msg
    assert ei.value.dst == "worker0" and ei.value.src == "worker0"
    assert resilience.get_counter("rpc.send_dropped") >= 3


def test_no_reply_without_retry_times_out_naming_peer(rpc_env):
    set_flags({"FLAGS_fault_injection": "rpc.send_drop:*"})
    with pytest.raises(CommTimeoutError, match="worker0"):
        rpc.rpc_sync("worker0", operator.add, args=(1, 1), timeout=0.5)


def test_resend_after_without_retry_tolerates_slow_execution(rpc_env):
    """resend_after with NO retry budget must not convert a slow
    execution into 'exhausted retry budget': one attempt means no
    resends ever happen (so no claimed receipt can exist to save the
    call) — only the overall timeout bounds it."""
    _slow_gate.clear()
    try:
        fut = rpc.rpc_async("worker0", _slow_call, timeout=30.0,
                            resend_after=0.1)
        threading.Timer(1.0, _slow_gate.set).start()
        assert fut.wait() == "slow done"  # NOT CommTimeoutError at ~0.35s
    finally:
        _slow_gate.set()


def test_retry_without_timeout_still_resends_and_raises(rpc_env):
    """retry= with neither timeout nor resend_after must still re-post
    (default cadence) and exhaust — not silently disable the budget and
    hang forever on a lost send."""
    set_flags({"FLAGS_fault_injection": "rpc.send_drop:*"})
    t0 = time.monotonic()
    with pytest.raises(CommTimeoutError, match="retry budget"):
        rpc.rpc_sync("worker0", operator.add, args=(1, 1), retry=2)
    assert time.monotonic() - t0 < rpc.DEFAULT_RESEND_AFTER_S * 2 + 5.0
    assert resilience.get_counter("rpc.resend") >= 1


def test_timeout_gcs_claimed_and_reply_keys(rpc_env):
    """A caller that gives up must not leave its claimed receipt (or a
    reply that landed after it stopped checking) in the store forever."""
    store = rpc_env
    _slow_gate.clear()
    try:
        fut = rpc.rpc_async("worker0", _slow_call, timeout=0.8,
                            retry=3, resend_after=0.1)
        with pytest.raises(CommTimeoutError):
            fut.wait()
        # the resends were dropped as in-flight duplicates, so the
        # claimed marker exists right up until the abandon-GC removes it
        assert resilience.get_counter("rpc.claimed_wait") >= 1
        assert not store.check(f"rpc/claimed/{fut._id}")
        assert not store.check(f"rpc/reply/{fut._id}")
    finally:
        _slow_gate.set()


def test_evicted_unconsumed_replies_are_gcd():
    """An abandoned caller's reply key is deleted callee-side when its
    id falls out of the dedup window — store growth stays bounded even
    when the caller never consumes."""
    store = rpc.init_rpc("evict", rank=0, world_size=1, dedup_window=4)
    try:
        fut = rpc.rpc_async("evict", operator.add, args=(1, 1),
                            timeout=10.0)
        deadline = time.monotonic() + 10
        while (not store.check(f"rpc/reply/{fut._id}")
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert store.check(f"rpc/reply/{fut._id}")
        for i in range(8):  # roll the abandoned id out of the window
            rpc.rpc_sync("evict", operator.add, args=(i, 1), timeout=10.0)
        deadline = time.monotonic() + 10
        while (store.check(f"rpc/reply/{fut._id}")
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert not store.check(f"rpc/reply/{fut._id}")
    finally:
        rpc.shutdown()


def test_shutdown_restores_switch_interval():
    import sys

    prev = sys.getswitchinterval()
    rpc.init_rpc("swint", rank=0, world_size=1)
    try:
        assert sys.getswitchinterval() == 0.0005
    finally:
        rpc.shutdown()
    assert sys.getswitchinterval() == prev


def test_duplicate_post_executes_once(rpc_env):
    """Transport-level rid idempotency: the same encoded request posted
    twice (a duplicated message on the wire) executes once; the second
    delivery hits the dedup cache — its cached reply is re-written, the
    side effect is NOT repeated."""
    from paddle_tpu.distributed.rpc import _encode, _fn_ref, _post

    del _effects[:]
    store = rpc_env
    state = rpc._state
    fut = rpc.rpc_async("worker0", _record_effect, args=("dup",),
                        timeout=30.0)
    assert fut.wait() == 1
    assert not store.check(f"rpc/reply/{fut._id}")  # consumed + GC'd
    # duplicate the message on the wire: re-post the SAME request blob
    req = {"id": fut._id, "fn": _fn_ref(_record_effect),
           "args": ("dup",), "kwargs": {}}
    _post(state, "worker0", _encode(req))
    deadline = time.monotonic() + 10
    while (not store.check(f"rpc/reply/{fut._id}")
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert store.check(f"rpc/reply/{fut._id}")  # cached reply re-written
    assert _effects == ["dup"]                  # NOT re-executed
    assert resilience.get_counter("rpc.redelivered") == 1
    store.delete_key(f"rpc/reply/{fut._id}")


def test_dedup_window_is_bounded():
    rpc.init_rpc("bounded", rank=0, world_size=1, dedup_window=8)
    try:
        state = rpc._state
        for i in range(30):
            rpc.rpc_sync("bounded", operator.add, args=(i, 1))
        assert len(state.seen) <= 8
    finally:
        rpc.shutdown()


# -------------------------------------- crash recovery (ack-after-execute)


def test_unacked_slot_is_reserved_after_restart():
    """Ack-after-execute: a slot a dead dispatcher claimed but never
    acked survives in the store; the next incarnation re-serves it
    (resume_inbox=True) and counts the replay."""
    from paddle_tpu.distributed.rpc import _encode
    from paddle_tpu.distributed.store import TCPStore

    master = TCPStore(is_master=True)  # survives dispatcher restarts
    endpoint = f"127.0.0.1:{master.port}"
    try:
        # the store state a crashed dispatcher leaves behind: a request
        # enqueued exactly as _post would, claimed (counter bumped) but
        # never acked — the slot key is still there
        req = {"id": "deadbeef01", "fn": "operator:add", "args": (20, 22)}
        slot = int(master.add("rpc/inbox/crashy", 1)) - 1
        master.add("rpc/inbox/crashy/claimed", 1)
        master.set(f"rpc/inbox/crashy/{slot}", _encode(req))

        rpc.init_rpc("crashy", rank=1, master_endpoint=endpoint,
                     resume_inbox=True)
        try:
            deadline = time.monotonic() + 10
            while (not master.check("rpc/reply/deadbeef01")
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert master.check("rpc/reply/deadbeef01"), \
                "unacked slot not re-served"
            assert resilience.get_counter("rpc.redelivered") >= 1
        finally:
            rpc.shutdown()
    finally:
        master.close()


def test_recovery_serves_slot_enqueued_in_the_write_gap():
    """At-least-once across restart: a slot whose inbox counter bump
    landed but whose blob write hadn't yet (the enqueue/write gap) must
    be served once the blob lands — not silently skipped by recovery
    with the claimed counter advanced past it."""
    from paddle_tpu.distributed.rpc import _encode
    from paddle_tpu.distributed.store import TCPStore

    master = TCPStore(is_master=True)
    endpoint = f"127.0.0.1:{master.port}"
    try:
        slot = int(master.add("rpc/inbox/gappy", 1)) - 1  # bump landed
        rpc.init_rpc("gappy", rank=1, master_endpoint=endpoint,
                     resume_inbox=True)
        try:
            time.sleep(0.1)  # recovery has scanned; blob lands late
            req = {"id": "gap01", "fn": "operator:add", "args": (2, 3)}
            master.set(f"rpc/inbox/gappy/{slot}", _encode(req))
            deadline = time.monotonic() + 10
            while (not master.check("rpc/reply/gap01")
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert master.check("rpc/reply/gap01"), "in-gap slot dropped"
        finally:
            rpc.shutdown()
    finally:
        master.close()


def test_inbox_is_purged_before_the_name_is_published(monkeypatch):
    """A caller takes the worker's name in the store as "addressable"
    and may enqueue at once. The purge of a dead epoch's inbox
    (resume_inbox=False) has to be over by then, or it deletes that
    request too: a fleet router's first ``fingerprint`` call to a replica
    then got no reply within its 10 s (test_fleet_trace / test_transfer
    under the suite's load, the replica's serve thread starting late)."""
    from paddle_tpu.distributed.store import TCPStore

    master = TCPStore(is_master=True)
    published = []
    real = rpc._recover_inbox

    def spy(state):
        published.append(master.check(f"rpc/worker/{state.name}"))
        return real(state)

    monkeypatch.setattr(rpc, "_recover_inbox", spy)
    try:
        rpc.init_rpc("prompt", rank=1,
                     master_endpoint=f"127.0.0.1:{master.port}",
                     resume_inbox=False)
        try:
            assert master.check("rpc/worker/prompt")
            assert published == [False]
        finally:
            rpc.shutdown()
    finally:
        master.close()


def test_purge_inbox_on_restart_for_serving_replicas():
    """resume_inbox=False (serving replicas): a fresh incarnation purges
    unacked slots instead of replaying a dead fleet epoch's traffic."""
    from paddle_tpu.distributed.rpc import _encode
    from paddle_tpu.distributed.store import TCPStore

    master = TCPStore(is_master=True)
    endpoint = f"127.0.0.1:{master.port}"
    try:
        req = {"id": "cafebabe02", "fn": "operator:add", "args": (1, 2)}
        slot = int(master.add("rpc/inbox/fresh", 1)) - 1
        master.add("rpc/inbox/fresh/claimed", 1)
        master.set(f"rpc/inbox/fresh/{slot}", _encode(req))

        rpc.init_rpc("fresh", rank=1, master_endpoint=endpoint,
                     resume_inbox=False)
        try:
            deadline = time.monotonic() + 10
            while (master.check(f"rpc/inbox/fresh/{slot}")
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert not master.check(f"rpc/inbox/fresh/{slot}")
            assert resilience.get_counter("rpc.purged") == 1
            time.sleep(0.1)
            assert not master.check("rpc/reply/cafebabe02")  # not executed
        finally:
            rpc.shutdown()
    finally:
        master.close()
