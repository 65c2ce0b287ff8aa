"""Hardened RPC — remote function execution between processes.

Analog of /root/reference/python/paddle/distributed/rpc/ (init_rpc,
rpc_sync, rpc_async, shutdown over brpc services,
paddle/fluid/distributed/rpc/). TPU-native transport: the native TCPStore
(tcp_store.cpp) carries length-framed request/response blobs; each worker
runs a dispatcher serving calls addressed to its name. Payloads are
serialized with an in-memory container format — function identity travels
as ``module:qualname`` and is resolved by import, never unpickled code.

This is the transport under the CROSS-PROCESS serving fleet
(models/remote.py ``RemoteFrontend`` → ``ReplicaServer``), so it carries
the production-robustness contract the fleet drills assert:

* **At-least-once delivery, ack-after-execute** — an inbox slot key is
  deleted only AFTER the call executed and its reply was written. A
  dispatcher that crashes mid-call leaves the slot key behind; the next
  dispatcher incarnation re-serves it (``resume_inbox=True``, counted
  ``rpc.redelivered``) or purges it (``resume_inbox=False`` — serving
  replicas, where the router's failover owns recovery).
* **Rid-idempotent dedup on the callee** — every request carries a
  caller-minted id; a retried send of the same id never re-executes.
  In-progress duplicates are dropped; completed ones get their cached
  reply re-written (the reply, not the send, may have been the drop).
* **Bounded store growth** — reply keys are GC'd by ``_Future.wait``
  after consumption, inbox slot keys by the post-execute ack; only the
  two per-worker inbox counters persist.
* **Worker-pool dispatch** — ``num_workers`` threads execute claimed
  calls, so one slow ``results()`` poll cannot head-of-line-block a
  ``health()`` probe.
* **Typed remote errors** — a remote exception travels as
  (module, type, message, traceback) and re-raises CALLER-side as its
  real class when it is a known resilience/builtin type
  (``TimeoutError``, ``ServingUnavailable``, ``CommTimeoutError``, …);
  unknown types surface as :class:`RpcRemoteError`.
* **Retry-budgeted resends** — ``rpc_async(..., retry=...)`` re-posts
  the request when no reply lands within ``resend_after`` seconds; an
  exhausted budget raises :class:`~..core.resilience.CommTimeoutError`
  naming the peer and the request. The budget covers DELIVERY only:
  when the callee drops a resend as an in-flight duplicate it writes a
  ``rpc/claimed/{id}`` receipt marker, and a caller that exhausts its
  resends but sees the marker keeps waiting (counted
  ``rpc.claimed_wait``) until the overall timeout — a slow execution
  (first-traffic compile, a lock held by a decode segment) must not
  read as a lost message.
* **Deterministic fault sites** — ``rpc.send_drop`` (the send vanishes
  on the wire), ``rpc.reply_drop`` (the reply vanishes; the callee has
  executed), ``rpc.delay`` (the callee stalls one call) drill all of
  the above through ``FLAGS_fault_injection``.
"""
from __future__ import annotations

import builtins
import importlib
import json
import threading
import time
import uuid
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..core import resilience as _res
from ..core import telemetry
from ..core.resilience import (
    CommTimeoutError,
    Deadline,
    InjectedFault,
    bump_counter,
    inject,
    logger,
)

__all__ = [
    "init_rpc", "rpc_sync", "rpc_async", "shutdown", "get_worker_info",
    "RpcRemoteError",
]

_state = None
_state_lock = threading.Lock()

# seconds one rpc.delay fault stalls the callee (long enough that a
# concurrent probe call provably overtakes the stalled one)
DELAY_FAULT_S = 0.25

# resend cadence when a retry budget is given without an overall timeout
# or an explicit resend_after — the budget must still re-post (a silently
# inert retry= is a caller hang on the first lost send)
DEFAULT_RESEND_AFTER_S = 1.0


class RpcRemoteError(RuntimeError):
    """A remote call raised an exception type the caller cannot (or must
    not) reconstruct; the remote type/message travel in the text."""


# caller-observed round trip (post → reply consumed), labeled by callee:
# the wire half of the fleet's transport-overhead picture, merged into
# fleet_metrics() like every other registry series
_M_RTT = telemetry.histogram(
    "rpc.roundtrip_s", "rpc_sync/rpc_async round-trip, post -> reply")


class WorkerInfo:
    def __init__(self, name, rank, ip=None, port=None):
        self.name = name
        self.rank = rank
        self.ip = ip
        self.port = port


class _RpcState:
    def __init__(self, name, rank, world_size, store, serve_store,
                 num_workers, poll, dedup_window, resume_inbox):
        self.name = name
        self.rank = rank
        self.world_size = world_size
        self.store = store          # caller-side connection
        self.serve_store = serve_store  # dispatcher's OWN connection:
        # a blocking native GET holds the per-connection mutex, so server
        # and client must not share one socket; the worker pool shares
        # this one because every op on it is a short non-blocking call
        self.num_workers = int(num_workers)
        self.poll = float(poll)
        self.dedup_window = int(dedup_window)
        self.resume_inbox = bool(resume_inbox)
        self.stop = threading.Event()
        self.thread = None
        self.pool = None
        # rid-idempotent dedup: req id -> "pending" | encoded reply blob
        self.seen: dict[str, object] = {}
        self.done_order: deque[str] = deque()
        self.lock = threading.Lock()
        # switch interval init_rpc overrode, to restore on shutdown()
        # (None when init_rpc left it alone)
        self.prev_switch_interval = None


# --------------------------------------------------------------- codec

def _encode(obj) -> bytes:
    """In-memory container: 8-byte head length + JSON head + raw tensor
    blob. Tensors/ndarrays travel as dtype/shape-tagged byte ranges (no
    tempfile round-trip); dicts with non-string keys (a results map
    keyed by int rid, a queue_by_priority snapshot) survive JSON via an
    item-list tag."""
    tensors: list[np.ndarray] = []

    def walk(o):
        from ..core.tensor import Tensor

        if isinstance(o, Tensor):
            tensors.append(np.ascontiguousarray(np.asarray(o._value)))
            return {"@rpc_t": len(tensors) - 1}
        if isinstance(o, np.ndarray):
            tensors.append(np.ascontiguousarray(o))
            return {"@rpc_t": len(tensors) - 1}
        if isinstance(o, dict):
            if all(isinstance(k, str) for k in o):
                return {k: walk(v) for k, v in o.items()}
            return {"@rpc_d": [[walk(k), walk(v)] for k, v in o.items()]}
        if isinstance(o, (list, tuple)):
            return {"@rpc_l": [walk(v) for v in o],
                    "@rpc_tuple": isinstance(o, tuple)}
        if isinstance(o, np.integer):
            return int(o)
        if isinstance(o, np.floating):
            return float(o)
        if isinstance(o, np.bool_):
            return bool(o)
        return o

    tree = walk(obj)
    metas = []
    blobs = []
    offset = 0
    for arr in tensors:
        raw = arr.tobytes()
        metas.append({"dtype": arr.dtype.name, "shape": list(arr.shape),
                      "offset": offset, "nbytes": len(raw)})
        offset += len(raw)
        blobs.append(raw)
    head = json.dumps({"tree": tree, "tensors": metas}).encode()
    return len(head).to_bytes(8, "little") + head + b"".join(blobs)


def _decode(data: bytes):
    hlen = int.from_bytes(data[:8], "little")
    head = json.loads(data[8:8 + hlen].decode())
    blob = data[8 + hlen:]
    tensors = []
    for meta in head["tensors"]:
        raw = blob[meta["offset"]:meta["offset"] + meta["nbytes"]]
        tensors.append(np.frombuffer(raw, dtype=np.dtype(meta["dtype"]))
                       .reshape(meta["shape"]).copy())

    def walk(o):
        if isinstance(o, dict):
            if "@rpc_t" in o:
                return tensors[o["@rpc_t"]]
            if "@rpc_d" in o:
                return {walk(k): walk(v) for k, v in o["@rpc_d"]}
            if "@rpc_l" in o:
                vals = [walk(v) for v in o["@rpc_l"]]
                return tuple(vals) if o.get("@rpc_tuple") else vals
            return {k: walk(v) for k, v in o.items()}
        return o

    return walk(head["tree"])


def _fn_ref(fn) -> str:
    return f"{fn.__module__}:{fn.__qualname__}"


def _resolve(ref: str):
    mod, _, qual = ref.partition(":")
    obj = importlib.import_module(mod)
    for part in qual.split("."):
        obj = getattr(obj, part)
    return obj


# ------------------------------------------------------- remote errors

# resilience types that must cross the wire as themselves: a router
# catching ServingUnavailable / TimeoutError from a RemoteFrontend call
# classifies replica-level unavailability exactly like the in-process
# path would
_TYPED_ERRORS = {
    cls.__name__: cls
    for cls in (
        _res.CommTimeoutError, _res.InjectedFault,
        _res.CheckpointCorruptionError, _res.PeerFailureError,
        _res.ServingUnavailable, _res.StaleLeaderError,
        _res.TenantQuotaExceeded,
    )
}


def _describe_error(e: Exception) -> dict:
    import traceback

    return {
        "type": type(e).__name__,
        "module": type(e).__module__,
        "message": str(e),
        "traceback": traceback.format_exc(limit=16),
    }


def _raise_remote(err: dict, to):
    """Re-raise a remote exception as its real class when it is a known
    type (builtins or the resilience registry); otherwise wrap it in
    :class:`RpcRemoteError` with the remote type in the text. The remote
    traceback rides along as ``e.remote_traceback`` either way."""
    name = err.get("type", "Exception")
    msg = err.get("message", "")
    cls = _TYPED_ERRORS.get(name)
    if cls is None and err.get("module") == "builtins":
        cand = getattr(builtins, name, None)
        if isinstance(cand, type) and issubclass(cand, Exception):
            cls = cand
    exc = None
    if cls is not None:
        try:
            exc = cls(msg)
        except Exception:  # exotic constructor signature: fall through
            exc = None
    if exc is None:
        exc = RpcRemoteError(f"rpc remote error on {to!r}: {name}: {msg}")
    exc.remote_traceback = err.get("traceback")
    raise exc


# ---------------------------------------------------------- dispatcher

def _inbox(name: str) -> str:
    return f"rpc/inbox/{name}"


def _execute(state: _RpcState, slot: int, redelivered=False):
    """Execute one claimed inbox slot on a pool worker: dedup by request
    id, run the call, write the reply, and only then ACK by deleting the
    slot key — a crash anywhere before that leaves the slot for the next
    dispatcher incarnation (at-least-once)."""
    store = state.serve_store
    key = f"{_inbox(state.name)}/{slot}"
    try:
        # the enqueue counter bump and the slot write are two store ops:
        # a claim can land in between (poll at the transport's own
        # cadence, not the store's 50ms rendezvous slices), and a caller
        # dying in between leaves a phantom slot
        slot_wait = Deadline(5.0)
        while not store.check(key):
            if state.stop.is_set():
                # shutting down: leave the slot (if its blob ever lands)
                # for the next incarnation instead of hot-spinning the
                # pool worker through the full phantom window
                return
            if slot_wait.expired():
                bump_counter("rpc.phantom_slot")
                return
            # the blob normally lands within the caller's next store op —
            # poll hot; the transport's fixed per-call latency is the
            # fleet's rpc-overhead gate
            time.sleep(0.0005)
        # single-consumer read of a key check() just proved: skip get()'s
        # redundant check poll (this slot is ours alone until we ack it)
        data = store.get_now(key)
        req = _decode(data)
        req_id = req["id"]
        cached = None
        in_flight = False
        with state.lock:  # bookkeeping only — store round-trips under
            # this lock would serialize the whole worker pool's dedup
            st = state.seen.get(req_id)
            if st is None:
                state.seen[req_id] = "pending"
            elif isinstance(st, (bytes, bytearray)):
                cached = bytes(st)   # done: the REPLY may have dropped
            else:
                in_flight = True
        if in_flight:
            # still executing on another pool worker: this duplicate IS
            # the caller resending because the execution is slow — write
            # the receipt marker (delivery is confirmed; the resend
            # budget covers delivery, not execution) and drop it: the
            # in-flight call's reply serves the retried future too. Lazy
            # marker: the no-retry hot path pays no extra store op.
            store.set(f"rpc/claimed/{req_id}", b"1")
            bump_counter("rpc.redelivered")
            store.delete_key(key)
            return
        if redelivered or cached is not None:
            bump_counter("rpc.redelivered")
        if cached is not None:
            store.set(f"rpc/reply/{req_id}", cached)
            store.delete_key(key)
            return
        try:
            inject("rpc.delay")
        except InjectedFault:
            bump_counter("rpc.delayed")
            time.sleep(DELAY_FAULT_S)
        try:
            fn = _resolve(req["fn"])
            result = fn(*req.get("args", ()), **dict(req.get("kwargs", {})))
            payload = {"ok": True, "result": result}
        except Exception as e:  # travels typed; see _raise_remote
            payload = {"ok": False, "error": _describe_error(e)}
        try:
            blob = _encode(payload)
        except Exception as e:  # unserializable result: the ERROR is the
            # reply — leaving seen[req_id] at "pending" with no reply
            # would strand the caller until its overall timeout (every
            # resend dropped as an in-flight duplicate) and, under
            # resume_inbox, poison every future incarnation with the
            # same unacked slot
            payload = {"ok": False, "error": _describe_error(e)}
            blob = _encode(payload)
        evicted = []
        with state.lock:
            state.seen[req_id] = blob
            state.done_order.append(req_id)
            while len(state.done_order) > state.dedup_window:
                old = state.done_order.popleft()
                state.seen.pop(old, None)
                evicted.append(old)
        try:
            inject("rpc.reply_drop")
            store.set(f"rpc/reply/{req_id}", blob)
        except InjectedFault:
            bump_counter("rpc.reply_dropped")
        # the ACK: after execute + reply. The dedup entry above makes a
        # crash between reply and ack (or a dropped reply) harmless —
        # the redelivery finds the cached blob instead of re-executing.
        store.delete_key(key)
        for old in evicted:
            # a reply/claim still in the store this far past its call
            # (dedup_window completions later) was abandoned by its
            # caller — wait() GCs on consumption — so the eviction owns
            # keeping store growth bounded
            store.delete_key(f"rpc/reply/{old}")
            store.delete_key(f"rpc/claimed/{old}")
    except Exception as e:  # noqa: BLE001 — a broken slot must not kill
        # the pool worker; count it and keep serving
        bump_counter("rpc.dispatch_error")
        logger.warning("rpc dispatcher failed serving %s: %s", key, e)


def _recover_inbox(state: _RpcState):
    """Scan the inbox a previous dispatcher incarnation left behind:
    slot keys that still exist were claimed (or never claimed) but NOT
    acked. ``resume_inbox=True`` re-serves them (at-least-once);
    ``False`` purges them (serving replicas: a fresh process must not
    replay a dead fleet epoch's traffic — the router's failover owns
    those requests)."""
    store = state.serve_store
    inbox = _inbox(state.name)
    n = int(store.add(inbox, 0))
    claimed = int(store.add(f"{inbox}/claimed", 0))
    for slot in range(n):
        if not store.check(f"{inbox}/{slot}"):
            # below the old claimed watermark a missing key means
            # executed-and-acked. At or above it, the slot was never
            # claimed: its blob is still in the enqueue/write gap (the
            # caller's counter bump landed first) — _execute's slot_wait
            # tolerates exactly that gap, so serve it rather than drop a
            # request the caller believes enqueued. (Purge mode skips
            # it: there is no key to delete yet, and the router's
            # failover owns the dead epoch's traffic.)
            if slot >= claimed and state.resume_inbox:
                state.pool.submit(_execute, state, slot, True)
            continue
        if state.resume_inbox:
            state.pool.submit(_execute, state, slot, True)
        else:
            bump_counter("rpc.purged")
            store.delete_key(f"{inbox}/{slot}")
    if claimed < n:
        store.add(f"{inbox}/claimed", n - claimed)


def _serve(state: _RpcState):
    """Claim loop: hand every enqueued slot to the worker pool. Claiming
    is a plain counter bump — this thread is the only claimer for this
    worker name, so slots dispatch exactly once per incarnation."""
    store = state.serve_store
    inbox = _inbox(state.name)
    hot_until = 0.0  # monotonic: poll hot while traffic is flowing
    while not state.stop.is_set():
        try:
            n = int(store.add(inbox, 0))
            claimed = int(store.add(f"{inbox}/claimed", 0))
            if claimed >= n:
                # adaptive cadence: recent traffic predicts more — a hot
                # claim loop keeps per-call latency out of the fleet's
                # rpc-overhead budget; an idle one backs off to ``poll``
                hot = time.monotonic() < hot_until
                state.stop.wait(0.0005 if hot else state.poll)
                continue
            slot = int(store.add(f"{inbox}/claimed", 1)) - 1
            hot_until = time.monotonic() + 0.25
            state.pool.submit(_execute, state, slot)
        except Exception as e:  # noqa: BLE001 — transient store failure
            bump_counter("rpc.dispatch_error")
            logger.warning("rpc claim loop error for %r: %s",
                           state.name, e)
            state.stop.wait(max(state.poll, 0.05))


# ---------------------------------------------------------------- API

def init_rpc(name, rank=None, world_size=None, master_endpoint=None,
             num_workers=4, poll=0.005, dedup_window=1024,
             resume_inbox=True):
    """Join the RPC group (reference rpc/init_rpc). Single-host
    multi-thread or multi-process via the shared TCPStore endpoint.

    ``num_workers`` pool threads execute incoming calls concurrently (a
    slow call cannot head-of-line-block a health probe); ``poll`` is the
    claim/reply poll interval; ``dedup_window`` bounds the callee-side
    request-id dedup cache; ``resume_inbox`` selects whether unacked
    slots from a crashed predecessor are re-served or purged."""
    global _state
    import sys

    from .store import TCPStore

    with _state_lock:
        if _state is not None:
            raise RuntimeError("init_rpc already called; shutdown() first")
        # every store op is a TCP round-trip served by (and serving)
        # threads that fight CPU-bound Python for the GIL; the default
        # 5ms switch interval turns each of the transport's ~9 ops/call
        # into a potential 5ms stall. An RPC group member prioritizes
        # transport responsiveness (shutdown() restores the old value).
        prev_switch = sys.getswitchinterval()
        if prev_switch > 0.0005:
            sys.setswitchinterval(0.0005)
        else:
            prev_switch = None
        if master_endpoint:
            host, _, port = master_endpoint.rpartition(":")
            store = TCPStore(host or "127.0.0.1", int(port),
                             is_master=(rank in (0, None)))
            serve_store = TCPStore(host or "127.0.0.1", store.port)
        else:
            store = TCPStore(is_master=(rank in (0, None)))
            serve_store = TCPStore(port=store.port)
        _state = _RpcState(name, rank or 0, world_size or 1, store,
                           serve_store, num_workers, poll, dedup_window,
                           resume_inbox)
        _state.prev_switch_interval = prev_switch
        _state.pool = ThreadPoolExecutor(
            max_workers=_state.num_workers,
            thread_name_prefix=f"rpc-{name}")
        # the predecessor's inbox is recovered (or purged) BEFORE the
        # name is published: the name in the store tells a caller that
        # this worker is addressable, and a request enqueued between the
        # publication and a purge would go with the dead epoch's traffic
        # (its caller then waits out a whole timeout for no reply)
        try:
            _recover_inbox(_state)
        except Exception as e:  # noqa: BLE001 — recovery is best-effort
            bump_counter("rpc.dispatch_error")
            logger.warning("rpc inbox recovery failed for %r: %s", name, e)
        _state.store.set(f"rpc/worker/{name}", str(rank or 0))
        _state.thread = threading.Thread(target=_serve, args=(_state,),
                                         daemon=True,
                                         name=f"rpc-serve-{name}")
        _state.thread.start()
        return _state.store


def get_worker_info(name=None, timeout=30.0):
    """Look up a worker by name, honoring ``timeout`` — an unknown name
    raises ``TimeoutError`` naming the worker instead of blocking on the
    store's (900s) rendezvous default forever."""
    if _state is None:
        raise RuntimeError("call init_rpc first")
    if name is None:
        return WorkerInfo(_state.name, _state.rank)
    key = f"rpc/worker/{name}"
    deadline = Deadline(timeout)
    while not _state.store.check(key):
        if deadline.expired():
            raise TimeoutError(
                f"rpc worker {name!r} not registered within {timeout}s")
        time.sleep(min(0.05, max(_state.poll, 0.001)))
    rank = int(_state.store.get(key).decode())
    return WorkerInfo(name, rank)


class _Future:
    """Reply handle for one ``rpc_async`` call. ``wait`` polls the reply
    key, GC's it after consumption, resends the request on the retry
    budget, and re-raises remote errors typed."""

    def __init__(self, req_id, state, to, what, timeout=None,
                 max_attempts=1, resend_after=None, resend=None):
        self._id = req_id
        self._state = state
        self._to = to
        self._what = what
        self._timeout = timeout      # rpc_async's default overall budget
        self._max_attempts = max(int(max_attempts), 1)
        self._resend_after = resend_after
        self._resend = resend
        self._done = False
        self._result = None
        self._error = None
        self._t0 = time.monotonic()  # rpc.roundtrip_s anchor

    def done(self) -> bool:
        return (self._done
                or self._state.store.check(f"rpc/reply/{self._id}"))

    def _gc(self):
        """Best-effort key cleanup when this call is abandoned (a
        timeout raise): the claimed receipt and any reply that landed
        after we stopped checking must not live in the store forever. A
        reply the callee writes AFTER this runs is GC'd callee-side on
        dedup-window eviction."""
        store = self._state.store
        try:
            store.delete_key(f"rpc/claimed/{self._id}")
            store.delete_key(f"rpc/reply/{self._id}")
        except Exception:  # noqa: BLE001 — cleanup must not mask the
            # timeout being raised
            bump_counter("rpc.gc_error")

    def wait(self, timeout=None):
        if self._done:
            if self._error is not None:
                raise self._error
            return self._result
        if timeout is None:
            timeout = self._timeout
        store = self._state.store
        key = f"rpc/reply/{self._id}"
        deadline = Deadline(timeout)
        per_try = self._resend_after
        if per_try is None:
            if timeout is not None:
                per_try = timeout / self._max_attempts
            elif self._max_attempts > 1:
                per_try = DEFAULT_RESEND_AFTER_S
        attempt = 1
        attempt_deadline = Deadline(per_try)
        # a budget of one attempt means NO resends — entering the
        # exhaustion branch with max_attempts=1 would raise "exhausted
        # retry budget" on a merely-slow execution (no duplicate was
        # ever posted, so no claimed receipt can exist to save it)
        resending = per_try is not None and self._max_attempts > 1
        while not store.check(key):
            if deadline.expired():
                self._gc()
                raise CommTimeoutError(
                    f"rpc {self._what} to {self._to!r} (request "
                    f"{self._id}) got no reply within {timeout}s "
                    f"({attempt} attempt(s))",
                    key=self._id, src=self._state.name, dst=self._to)
            if resending and attempt_deadline.expired():
                if attempt >= self._max_attempts:
                    # the budget covers DELIVERY, not execution: a
                    # claimed request is provably on the callee (its
                    # receipt marker exists — written when the callee
                    # dropped one of our resends as an in-flight
                    # duplicate), so stop resending and let the overall
                    # deadline bound the slow execution. The marker
                    # trails the last resend by one dispatch, so grant
                    # it a short grace before declaring the request
                    # lost and failing.
                    grace = Deadline(min(per_try, 0.25))
                    claimed = False
                    while not (grace.expired() or deadline.expired()):
                        if (store.check(f"rpc/claimed/{self._id}")
                                or store.check(key)):
                            claimed = True
                            break
                        time.sleep(min(self._state.poll, 0.001))
                    if claimed:
                        bump_counter("rpc.claimed_wait")
                        resending = False
                        continue
                    self._gc()
                    raise CommTimeoutError(
                        f"rpc {self._what} to {self._to!r} (request "
                        f"{self._id}) exhausted its retry budget "
                        f"({self._max_attempts} attempt(s), "
                        f"{per_try}s apart)",
                        key=self._id, src=self._state.name, dst=self._to)
                attempt += 1
                attempt_deadline = Deadline(per_try)
                bump_counter("rpc.resend")
                if self._resend is not None:
                    self._resend()
            # reply polls quantize every call's latency — cap at 1ms so
            # the transport's fixed cost stays inside the fleet's
            # rpc-overhead gate even when ``poll`` is coarser
            time.sleep(min(self._state.poll, 0.001))
        # single-consumer read of a key check() just proved exists; a
        # KeyError means the reply vanished between check and read (the
        # callee's abandoned-key eviction racing us) — re-enter the wait
        # loop: a resend re-executes (the dedup entry is gone too) or
        # the overall deadline bounds it
        try:
            payload = _decode(store.get_now(key))
        except KeyError:
            bump_counter("rpc.reply_vanished")
            return self.wait(timeout=deadline.remaining()
                             if deadline.expires_at is not None else None)
        # GC: a consumed reply (and, when resends could have left one,
        # the receipt marker) must not live in the store forever
        store.delete_key(key)
        if attempt > 1:
            store.delete_key(f"rpc/claimed/{self._id}")
        self._done = True
        if telemetry.enabled():
            _M_RTT.observe(time.monotonic() - self._t0, to=self._to)
        if not payload["ok"]:
            try:
                _raise_remote(payload["error"], self._to)
            except Exception as e:
                self._error = e
                raise
        self._result = payload["result"]
        return self._result


def _post(state: _RpcState, to: str, blob: bytes):
    """Enqueue one encoded request into ``to``'s inbox. The
    ``rpc.send_drop`` fault site models the send vanishing on the wire:
    the caller believes it sent; only the resend budget recovers it."""
    try:
        inject("rpc.send_drop")
    except InjectedFault:
        bump_counter("rpc.send_dropped")
        return
    inbox = _inbox(to)
    slot = int(state.store.add(inbox, 1)) - 1
    state.store.set(f"{inbox}/{slot}", blob)


def rpc_async(to, fn, args=(), kwargs=None, timeout=None, retry=None,
              resend_after=None):
    """Submit ``fn`` for execution on worker ``to`` (reference
    rpc_async). ``retry`` is a resend budget for lost sends/replies: an
    int attempt count or a ``RetryPolicy`` (its ``max_attempts`` is
    used); the request is re-posted (same id — the callee dedups) every
    ``resend_after`` seconds without a reply, and exhaustion raises
    ``CommTimeoutError`` naming the peer."""
    if _state is None:
        raise RuntimeError("call init_rpc first")
    req_id = uuid.uuid4().hex
    req = {"id": req_id, "fn": _fn_ref(fn), "args": tuple(args),
           "kwargs": dict(kwargs or {})}
    blob = _encode(req)
    state = _state
    _post(state, to, blob)
    if retry is None:
        max_attempts = 1
    elif isinstance(retry, int):
        max_attempts = retry
    else:
        max_attempts = retry.max_attempts
    # only a real resend budget keeps the encoded blob alive; a budget
    # of one attempt must not pin a multi-MB tensor payload for the
    # future's lifetime
    resend = ((lambda: _post(state, to, blob))
              if max_attempts > 1 else None)
    return _Future(req_id, state, to, _fn_ref(fn), timeout=timeout,
                   max_attempts=max_attempts, resend_after=resend_after,
                   resend=resend)


def rpc_sync(to, fn, args=(), kwargs=None, timeout=None, retry=None,
             resend_after=None):
    return rpc_async(to, fn, args, kwargs, retry=retry,
                     resend_after=resend_after).wait(timeout=timeout)


def shutdown():
    global _state
    with _state_lock:
        state, _state = _state, None
    if state is not None:
        state.stop.set()
        if state.thread:
            state.thread.join(2)
        if state.pool is not None:
            state.pool.shutdown(wait=True, cancel_futures=True)
        state.serve_store.close()
        state.store.close()
        if state.prev_switch_interval is not None:
            import sys

            # restore only if nobody tightened it further since init
            if sys.getswitchinterval() == 0.0005:
                sys.setswitchinterval(state.prev_switch_interval)
