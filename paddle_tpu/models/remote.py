"""Cross-process replica handles: a ``ServingFrontend`` behind RPC.

PR 6's replica fleet was in-process handles — ``ServingRouter`` called
``ServingFrontend`` methods directly. This module puts the same surface
over the hardened RPC transport (``distributed/rpc.py``) so router and
replicas live in DIFFERENT processes and the failure modes that only
exist across a process boundary (replica death mid-decode, dropped or
duplicated messages, slow replies) are survivable:

* :class:`ReplicaServer` — hosts a frontend behind the RPC dispatcher in
  the REPLICA process. A pump thread drives ``step()`` continuously (the
  replica serves autonomously; the router never remote-pumps), a lock
  serializes frontend access against the dispatcher's worker pool, and
  ``submit`` is **rid-idempotent**: a redelivered/retried submit for a
  rid that is still live here never double-enqueues.
* :class:`RemoteFrontend` — the ROUTER-side stub exposing the same
  ``submit / results / cancel / health / warmup / shutdown / ready /
  pending / fingerprint`` surface as ``ServingFrontend``, so
  ``ServingRouter.add_replica()`` takes local and remote replicas
  interchangeably. Every call carries a per-call timeout and a resend
  budget; transport failures surface as ``CommTimeoutError`` /
  ``ConnectionError`` (the router trips that replica's breaker), and
  remote resilience exceptions re-raise TYPED (``ServingUnavailable``
  when the addressed server is gone).
* :func:`replica_main` — worker-process entry: join the RPC group, host
  the frontend, heartbeat under the fleet prefix (the router's
  ``PeerFailureDetector`` lease covers SILENT death — SIGKILL mid-decode
  — which no transport error can report), publish the pid for drills,
  serve until a ``shutdown`` RPC or SIGTERM.

The bit-exact failover contract is unchanged: sampling keys are a pure
function of ``(engine seed, rid, token index)`` and the router owns the
rid space, so a request stranded on a dead replica PROCESS replays on a
survivor token-identical to the uninterrupted run.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import uuid

import numpy as np

from ..core import perfwatch, telemetry
from ..core.resilience import (
    Deadline,
    PeerFailureError,
    ServingUnavailable,
    StaleLeaderError,
    bump_counter,
    logger,
)
from .frontend import RequestResult

__all__ = ["ReplicaServer", "RemoteFrontend", "replica_main",
           "RPC_MASTER_ENV", "TRACE_DIR_ENV"]

# env var carrying the RPC master endpoint into replica processes
# (launch_fleet passes it through ``env=``)
RPC_MASTER_ENV = "PADDLE_RPC_MASTER"
# when set, a replica process exports its telemetry span sink as a
# Chrome-trace JSON here on clean exit — the per-process half a
# multi-process drill stitches (telemetry.stitch_chrome_traces) into one
# cross-process request timeline
TRACE_DIR_ENV = "PADDLE_TRACE_DIR"

_SERVERS: dict[str, "ReplicaServer"] = {}
_servers_lock = threading.Lock()


# methods that MUTATE frontend state: their fence check must hold the
# server lock, or a stale leader's call that passed a bare check could
# block behind a decode segment, outlive the new leader's repin, and
# then mutate state the new leader already inventoried
_MUTATING_METHODS = frozenset(
    {"submit", "cancel", "shutdown", "warmup", "repin",
     # KV page transfer: every leg either rebinds the engine's device
     # pools (export/import dispatch donated programs) or moves page
     # refcounts — all of it races the pump thread without the lock
     "export_pages", "transfer_chunk", "import_kv_chunk",
     "release_export", "drop_import"})


def _call(server, method, *args, _fence=None, **kwargs):
    """Module-level RPC target (function identity travels as
    ``module:qualname``): dispatch ``method`` on the named registered
    server. The envelope carries the server-side execution time so the
    caller can split transport overhead from real work. ``_fence`` is
    the caller's leader fencing token (HA router): a token below the
    highest this server has seen is a DEPOSED leader's late write and is
    rejected typed (``StaleLeaderError``) before the method can mutate —
    for mutating methods the check runs UNDER the server lock, so it is
    atomic with the mutation it guards (a repin cannot slip between the
    check and the call)."""
    with _servers_lock:
        srv = _SERVERS.get(server)
    if srv is None:
        raise ServingUnavailable(
            f"no replica server {server!r} registered in this process")
    t0 = time.monotonic()
    if method in _MUTATING_METHODS:
        # self._lock is an RLock: the method re-acquires it freely
        with srv._lock:
            srv.check_fence(_fence)
            result = getattr(srv, method)(*args, **kwargs)
    else:
        srv.check_fence(_fence)
        result = getattr(srv, method)(*args, **kwargs)
    return {"r": result, "exec_s": time.monotonic() - t0,
            "inc": srv.incarnation}


class ReplicaServer:
    """Host a ``ServingFrontend`` behind the RPC dispatcher.

    The server owns progress: a daemon pump thread steps the frontend
    whenever it has work, so results accumulate between the router's
    ``results`` polls. All frontend access (pump turns AND dispatcher
    worker-pool calls) is serialized under one lock — the engine is not
    thread-safe.
    """

    def __init__(self, frontend, name, poll=0.005, pump=True):
        self.frontend = frontend
        self.name = str(name)
        # a respawned replica process re-registers under the SAME worker
        # name and would silently answer a router still holding requests
        # the DEAD incarnation owned ("no results, perfectly healthy" —
        # the zombie-identity failure mode). Every envelope carries this
        # nonce; the stub pins the first one it sees and turns a change
        # into typed ServingUnavailable, which the router treats as
        # replica death (breaker trip + token_base failover).
        self.incarnation = uuid.uuid4().hex
        self.poll = float(poll)
        # highest leader fencing token served (HA router): its own tiny
        # lock — a fence check must answer while a decode segment holds
        # the frontend lock, and a stale leader must be rejected BEFORE
        # it can queue behind (and then mutate) live state
        self._fence = None
        self._fence_lock = threading.Lock()
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self.stopped = threading.Event()
        self._live: set = set()     # rids submitted, result not yet fetched
        self._busy_s = 0.0
        # health served from a snapshot refreshed every pump turn: a
        # router probe must not block on the frontend lock behind a
        # long decode segment or a first-call XLA compile
        self._health_cache = {}
        self._refresh_health()
        with _servers_lock:
            if self.name in _SERVERS:
                raise ValueError(
                    f"replica server {self.name!r} already registered")
            _SERVERS[self.name] = self
        self._pump_thread = None
        if pump:
            self._pump_thread = threading.Thread(
                target=self._pump, daemon=True,
                name=f"replica-pump-{self.name}")
            self._pump_thread.start()

    # ------------------------------------------------------------- pump

    def _pump(self):
        while not self._stop.is_set():
            busy = False
            t0 = time.monotonic()
            with self._lock:
                if self._stop.is_set():
                    break
                try:
                    if (self.frontend.pending()
                            or self.frontend.engine.has_work()):
                        busy = True
                        self.frontend.step()
                except Exception as e:  # noqa: BLE001 — a poisoned turn
                    # must not kill the pump; the frontend's own
                    # bisection/breaker machinery owns request verdicts
                    bump_counter("serving.remote_pump_error")
                    logger.warning("replica %r pump turn failed: %s",
                                   self.name, e)
                self._refresh_health()
            if busy:
                self._busy_s += time.monotonic() - t0
            else:
                self._stop.wait(self.poll)

    # ------------------------------------------------- the RPC surface

    def _refresh_health(self):
        """Refresh the lock-free health snapshot (caller holds _lock).
        Stamped with the SENDER's monotonic time + incarnation: health
        rides both direct probes and piggybacked results envelopes, and
        without a sender stamp a delayed envelope's stale snapshot could
        out-vote a fresher direct probe purely by arriving later — the
        router orders snapshots by these stamps, not by arrival."""
        try:
            snap = self.frontend.health()
            snap["_ts"] = time.monotonic()
            snap["_inc"] = self.incarnation
            self._health_cache = snap
        except Exception:  # noqa: BLE001 — a failed snapshot keeps the
            # previous view; the router's probe still answers
            bump_counter("serving.remote_health_error")
        if telemetry.enabled():
            # device-memory gauges ride this REPLICA's registry snapshot
            # to the store (rate-limited inside the watchdog), so
            # fleet_metrics() sees every process's HBM, not the router's
            perfwatch.memory_watchdog().maybe_poll()

    def check_fence(self, fence):
        """Leader-fencing gate (HA router): remember the highest fencing
        token ever served and reject anything lower — a deposed leader's
        late envelope must not mutate state the NEW leader now owns.
        ``None`` (a fleet without leader election) always passes."""
        if fence is None:
            return
        fence = int(fence)
        with self._fence_lock:
            cur = self._fence
            if cur is not None and fence < cur:
                bump_counter("serving.stale_leader_rejected")
                raise StaleLeaderError(
                    f"replica {self.name!r} rejects fencing token {fence}"
                    f": a newer leader (fence {cur}) has taken over")
            if cur is None or fence > cur:
                self._fence = fence

    def repin(self, fence):
        """Takeover handshake: the NEW leader records its fencing token
        here (everything the old leader sends afterwards bounces typed)
        and learns this replica's live request state — ``[[rid,
        token_base, tokens_so_far], ...]`` — so it can adopt running
        copies whose ``token_base`` is inside the journaled prefix and
        cancel/replay the rest."""
        self.check_fence(fence)
        with self._lock:
            prog = self.frontend.progress()
        return [[rid, base, np.asarray(toks, np.int32)]
                for rid, (base, toks) in prog.items()]

    def progress(self):
        """Live request progress rows (same shape as :meth:`repin`'s
        return) without the fence handshake."""
        with self._lock:
            prog = self.frontend.progress()
        return [[rid, base, np.asarray(toks, np.int32)]
                for rid, (base, toks) in prog.items()]

    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_s=None, rid=None, token_base=0, trace=None,
               tenant=None, hold_kv=False, kv_import=None):
        """Rid-idempotent admission: a rid still LIVE here (pending or
        finished-but-unfetched) is a duplicate of a retried/redelivered
        send — acknowledge it without double-enqueueing. ``trace`` is
        the router-minted telemetry trace id off the RPC envelope; the
        frontend's spans in THIS process stitch under it. ``tenant``
        rides the same envelope into the frontend's QoS lane;
        ``hold_kv``/``kv_import`` are the disaggregation legs (see
        ``ServingFrontend.submit``)."""
        with self._lock:
            if rid is not None and rid in self._live:
                bump_counter("serving.dup_submit")
                return rid
            got = self.frontend.submit(
                np.asarray(prompt, np.int32),
                max_new_tokens=max_new_tokens, priority=priority,
                deadline_s=deadline_s, rid=rid, token_base=token_base,
                trace=trace, tenant=tenant, hold_kv=hold_kv,
                kv_import=kv_import)
            self._live.add(got)
            return got

    def results(self, wait_s=0.0, progress=False):
        """Drain terminal results as ``[rows, pending, health,
        progress]`` where rows are ``[rid, status, tokens, reason,
        token_base]``, ``pending`` is the count of requests still
        working here, ``health`` is the lock-free snapshot, and
        ``progress`` is the live-request progress rows — the stub's
        ``results(wait=True)`` loop, the router's dispatch scoring AND
        its journal PROGRESS checkpoints all want these every round, and
        one envelope is one round-trip, not four. The progress rows are
        OPT-IN (``progress=True``, requested by journaling HA routers):
        they serialize every live request's emitted tokens, a wire tax a
        journal-less fleet should not pay per poll. Blocks up to
        ``wait_s`` for the pump to produce something — the router's
        poll loop rides this instead of hammering empty fetches."""
        deadline = Deadline(wait_s if wait_s and wait_s > 0 else None)
        while True:
            with self._lock:
                out = self.frontend.results()
            if out or deadline.expires_at is None or deadline.expired():
                break
            time.sleep(self.poll)
        return [self._drain_rows(out), int(self.frontend.pending()),
                dict(self._health_cache),
                self.progress() if progress else []]

    def _drain_rows(self, fetched):
        """Serialize fetched results into wire rows (the one definition
        of the row format ``RemoteFrontend`` unpacks), retiring each rid
        from the live set."""
        rows = []
        with self._lock:
            # the live set also gates submit()'s duplicate check — a
            # discard racing that check could re-admit a retiring rid
            for rid, res in fetched.items():
                self._live.discard(rid)
                rows.append([rid, res.status,
                             np.asarray(res.tokens, np.int32), res.reason,
                             int(getattr(res, "token_base", 0))])
        return rows

    def cancel(self, rid) -> bool:
        with self._lock:
            return bool(self.frontend.cancel(rid))

    # ------------------------------- KV page transfer (disaggregation)
    # All legs run under the server lock (they rebind the engine's
    # donated device pools / move page refcounts, racing the pump);
    # _call additionally fences them as mutating methods.

    def export_pages(self, rid):
        with self._lock:
            return self.frontend.export_pages(rid)

    def transfer_chunk(self, ticket, idx):
        with self._lock:
            return self.frontend.transfer_chunk(ticket, idx)

    def import_kv_chunk(self, meta, idx, payk, payv, crc):
        with self._lock:
            return self.frontend.import_kv_chunk(
                meta, int(idx), np.asarray(payk), np.asarray(payv),
                int(crc))

    def release_export(self, ticket) -> bool:
        with self._lock:
            return bool(self.frontend.release_export(ticket))

    def drop_import(self, ticket) -> bool:
        with self._lock:
            return bool(self.frontend.drop_import(ticket))

    def health(self) -> dict:
        # lock-free: the snapshot, not the live frontend — a probe must
        # return while a decode segment (or compile) holds the lock
        return dict(self._health_cache)

    def ready(self) -> bool:
        return bool(self._health_cache.get("ready", False))

    def pending(self) -> int:
        # len() reads are atomic enough for a progress poll; taking the
        # lock here would stall the router behind a decode segment
        return int(self.frontend.pending())

    def fingerprint(self):
        with self._lock:
            return tuple(self.frontend.fingerprint())

    def warmup(self):
        with self._lock:
            return self.frontend.warmup()

    def stats(self) -> dict:
        return {"busy_s": self._busy_s, "live": len(self._live)}

    def shutdown(self, drain=True):
        """Stop serving: drain (or hard-stop) the frontend, stop the
        pump, deregister — the NEXT call addressed here raises
        ``ServingUnavailable`` typed across the wire. Returns the final
        result rows the drain resolved (the server is gone after this
        reply, so they must ride IN it — ``RemoteFrontend`` stashes them
        for the router's post-shutdown collect)."""
        with _servers_lock:
            if _SERVERS.get(self.name) is self:
                del _SERVERS[self.name]
        self._stop.set()
        if (self._pump_thread is not None
                and self._pump_thread is not threading.current_thread()):
            self._pump_thread.join(5)
        with self._lock:
            self.frontend.shutdown(drain=drain)
            rows = self._drain_rows(self.frontend.results())
        self.stopped.set()
        return rows


class RemoteFrontend:
    """Client stub for a :class:`ReplicaServer` in another process —
    drop-in for ``ServingFrontend`` at the ``ServingRouter`` boundary.

    Every call is one RPC with a per-call ``timeout`` and a
    ``retry_attempts`` resend budget (the server dedups by request id,
    so a resent ``submit`` cannot double-enqueue). ``rpc_s`` / call
    accounting feeds the fleet bench's ``fleet_rpc_overhead_pct`` gate:
    transport overhead is round-trip time minus the server-side
    execution time each envelope reports.
    """

    is_remote = True

    def __init__(self, worker, server=None, timeout=60.0,
                 health_timeout=10.0, warmup_timeout=900.0,
                 retry_attempts=3, resend_after=None, results_wait=0.02):
        self.worker = str(worker)
        self.server = str(server if server is not None else worker)
        self.timeout = float(timeout)
        self.health_timeout = float(health_timeout)
        self.warmup_timeout = float(warmup_timeout)
        self.retry_attempts = int(retry_attempts)
        self.resend_after = resend_after
        self.results_wait = float(results_wait)
        self.rpc_s = 0.0           # caller-side round-trip time
        self.remote_exec_s = 0.0   # server-reported in-call time
        self.calls = 0
        # freshest health snapshot a results envelope carried — a free
        # ride-along the router uses instead of separate health probes
        self.piggyback_health = None
        # freshest live-request progress rows a results envelope carried
        # ({rid: (token_base, tokens)}) — feeds the router's journal
        # PROGRESS checkpoints without a separate wire round-trip. The
        # rows are requested only when want_progress is set (a journaling
        # HA router flips it): serializing every live request's tokens
        # per poll is a wire tax a journal-less fleet should not pay
        self.piggyback_progress = None
        self.want_progress = False
        # leader fencing token every call carries once set (HA router):
        # the server rejects anything below the highest it has served
        self.fence = None
        # first incarnation nonce seen from the server; a mismatch means
        # the replica process died and was respawned under our name
        self._incarnation = None
        self._closed = False
        # terminal rows the shutdown reply carried (the server drains,
        # answers ONCE, and deregisters — these are unreachable after)
        self._final: dict = {}

    # ------------------------------------------------------- transport

    def _rpc(self, method, *args, timeout=None, **kwargs):
        from ..distributed import rpc

        budget = self.timeout if timeout is None else float(timeout)
        resend_after = self.resend_after
        if resend_after is None:
            resend_after = max(budget / max(self.retry_attempts, 1), 0.05)
        if self.fence is not None:
            kwargs = dict(kwargs)
            kwargs["_fence"] = int(self.fence)
        t0 = time.monotonic()
        env = rpc.rpc_sync(self.worker, _call,
                           args=(self.server, method, *args),
                           kwargs=kwargs, timeout=budget,
                           retry=self.retry_attempts,
                           resend_after=resend_after)
        self.rpc_s += time.monotonic() - t0
        self.remote_exec_s += float(env.get("exec_s", 0.0))
        self.calls += 1
        inc = env.get("inc")
        if inc is not None:
            if self._incarnation is None:
                self._incarnation = inc
            elif inc != self._incarnation:
                # a RESPAWNED process answered under our server's name:
                # every request the dead incarnation held is gone, and a
                # healthy-looking reply from the zombie identity must
                # not mask that — surface it as replica death
                bump_counter("serving.replica_incarnation_changed")
                raise ServingUnavailable(
                    f"replica server {self.server!r} restarted "
                    f"(incarnation {inc[:8]} != pinned "
                    f"{self._incarnation[:8]}); its in-flight state "
                    f"is gone")
        return env["r"]

    def stats(self) -> dict:
        return {
            "rpc_s": self.rpc_s,
            "remote_exec_s": self.remote_exec_s,
            "rpc_overhead_s": max(self.rpc_s - self.remote_exec_s, 0.0),
            "calls": self.calls,
        }

    # ------------------------------------------- ServingFrontend surface

    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_s=None, rid=None, token_base=0, trace=None,
               tenant=None, hold_kv=False, kv_import=None):
        # a Deadline is monotonic and process-local: ship the REMAINING
        # seconds; the replica re-anchors it on its own clock (queue wait
        # there still counts against the budget). The telemetry trace id
        # (and QoS tenant) ride the same envelope — the replica's spans
        # and tenant lanes stitch under them.
        if isinstance(deadline_s, Deadline):
            rem = deadline_s.remaining()
            deadline_s = None if rem == float("inf") else max(rem, 0.0)
        return self._rpc("submit", np.asarray(prompt, np.int32),
                         max_new_tokens=max_new_tokens,
                         priority=int(priority), deadline_s=deadline_s,
                         rid=rid, token_base=int(token_base),
                         trace=trace, tenant=tenant,
                         hold_kv=bool(hold_kv), kv_import=kv_import)

    def results(self, wait=False, timeout=None) -> dict:
        """Pop terminal results. ``wait=True`` polls until the replica
        reports nothing pending (the server pumps itself — there is no
        remote step loop to drive); ``timeout`` overrides the per-call
        RPC budget (the router's dead-replica salvage passes a short
        one)."""
        out, self._final = dict(self._final), {}
        if self._closed:
            return out
        deadline = Deadline(timeout) if wait else None
        while True:
            rows, n_pending, health, progress = self._rpc(
                "results", wait_s=self.results_wait, timeout=timeout,
                progress=bool(self.want_progress))
            # free health/progress ride-alongs: the router refreshes its
            # dispatch scores and journal checkpoints from these instead
            # of separate round-trips
            self.piggyback_health = health
            self.piggyback_progress = {
                rid: (int(base), np.asarray(toks, np.int32))
                for rid, base, toks in progress}
            for rid, status, tokens, reason, base in rows:
                out[rid] = RequestResult(rid, status, tokens, reason,
                                         token_base=base)
            if not wait:
                return out
            if not rows and not n_pending:
                return out
            if deadline is not None and deadline.expired():
                return out

    def cancel(self, rid) -> bool:
        return bool(self._rpc("cancel", rid))

    # ------------------------------- KV page transfer (disaggregation)
    # One RPC per leg; the incarnation pin in _rpc is what turns a
    # respawned source into typed ServingUnavailable mid-transfer —
    # models/transfer.py classifies that as "re-prefill", never
    # silent corruption.

    def export_pages(self, rid):
        return self._rpc("export_pages", rid)

    def transfer_chunk(self, ticket, idx):
        return self._rpc("transfer_chunk", ticket, int(idx))

    def import_kv_chunk(self, meta, idx, payk, payv, crc):
        return self._rpc("import_kv_chunk", dict(meta), int(idx),
                         np.asarray(payk), np.asarray(payv), int(crc))

    def release_export(self, ticket) -> bool:
        return bool(self._rpc("release_export", ticket))

    def drop_import(self, ticket) -> bool:
        return bool(self._rpc("drop_import", ticket))

    def set_fence(self, fence):
        """Pin the leader fencing token every subsequent call carries —
        the router sets it on acquiring (or taking over) leadership."""
        self.fence = int(fence)

    def repin(self, fence):
        """Takeover handshake (see ``ReplicaServer.repin``): record the
        new leader's fence on the server and return the replica's live
        request state as ``{rid: (token_base, tokens_so_far)}``."""
        self.set_fence(fence)
        rows = self._rpc("repin", int(fence))
        return {rid: (int(base), np.asarray(toks, np.int32))
                for rid, base, toks in rows}

    def progress(self) -> dict:
        """Live request progress as ``{rid: (token_base, tokens)}``."""
        rows = self._rpc("progress", timeout=self.health_timeout)
        return {rid: (int(base), np.asarray(toks, np.int32))
                for rid, base, toks in rows}

    def health(self) -> dict:
        return self._rpc("health", timeout=self.health_timeout)

    def ready(self) -> bool:
        return bool(self._rpc("ready", timeout=self.health_timeout))

    def pending(self) -> int:
        return int(self._rpc("pending", timeout=self.health_timeout))

    def fingerprint(self):
        return tuple(self._rpc("fingerprint", timeout=self.health_timeout))

    def warmup(self):
        return self._rpc("warmup", timeout=self.warmup_timeout)

    def step(self):
        """No-op: the replica's own pump thread owns progress; the
        router's pump turn only needs the ``results`` fetch."""
        return None

    def shutdown(self, drain=True):
        with contextlib.suppress(ServingUnavailable):
            # already-deregistered server == already shut down
            rows = self._rpc("shutdown", drain=bool(drain),
                             timeout=self.warmup_timeout)
            for rid, status, tokens, reason, base in rows or ():
                self._final[rid] = RequestResult(rid, status, tokens,
                                                 reason, token_base=base)
        self._closed = True
        return True


# -------------------------------------------------- worker-process entry

def replica_main(build_frontend, rank=None, master_endpoint=None,
                 worker_name=None, server_name=None, fleet_prefix="fleet",
                 hb_interval=None, warmup=False, num_workers=4,
                 group=None):
    """Entry point for one replica worker process under
    ``launch_fleet``: join the RPC group at ``master_endpoint`` (default
    ``$PADDLE_RPC_MASTER``), host ``build_frontend()`` behind a
    :class:`ReplicaServer`, heartbeat under ``{fleet_prefix}/hb/{rank}``
    so the router's lease detector covers silent death, publish this
    pid at ``{fleet_prefix}/pid/{rank}`` (kill drills target it), and
    serve until a ``shutdown`` RPC or SIGTERM. Returns 0.

    ``group`` (a ``tp_serving.TPGroupMembership``) makes this process a
    TP-GROUP LEADER: the serve loop checks gang membership every
    membership interval, and a member death is GROUP-fatal — flight
    dump, hard stop, exit 1 for the supervisor to respawn (the fleet
    heartbeat lapses with this process, so the router sees exactly ONE
    replica death for the whole gang). A clean shutdown announces
    itself on the group store so the other members exit 0 instead of
    reading the leader's silence as a crash."""
    import signal
    import sys

    from ..distributed import rpc
    from ..distributed.store import TCPStore

    # the pump thread is CPU-bound in host bookkeeping between device
    # dispatches; at the default 5ms GIL switch interval every store op
    # the RPC dispatcher threads make waits up to 5ms for the GIL, which
    # multiplies into tens of ms of pure transport latency per call.
    # A serving replica prioritizes transport responsiveness.
    sys.setswitchinterval(0.0005)

    if rank is None:
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if master_endpoint is None:
        master_endpoint = os.environ[RPC_MASTER_ENV]
    worker = worker_name or f"replica{rank}"
    host, _, port = master_endpoint.rpartition(":")
    host = host or "127.0.0.1"

    # build + register the server BEFORE joining the RPC group: the
    # worker name appearing in the store is the router's "replica is
    # addressable" signal, so the server must already be there when the
    # first call lands (the frontend build takes seconds — a router
    # racing it would see ServingUnavailable)
    frontend = build_frontend()
    server = ReplicaServer(frontend, name=server_name or worker)
    if warmup:
        server.warmup()
    # rpc rank rank+1: the router process is rank 0 / store master
    rpc.init_rpc(worker, rank=rank + 1, master_endpoint=master_endpoint,
                 num_workers=num_workers, resume_inbox=False)

    # dedicated store client: the heartbeat daemon must not contend
    # with the dispatcher's connections
    hb_store = TCPStore(host, int(port))
    if hb_interval is None:
        # beat at the cadence the ROUTER's lease expects (it publishes
        # it at construction); a local-FLAGS-derived interval could
        # exceed a tighter router lease and flap this replica dead
        # while it is perfectly alive
        try:
            if hb_store.check(f"{fleet_prefix}/hb_interval"):
                hb_interval = float(
                    hb_store.get(f"{fleet_prefix}/hb_interval").decode())
        except Exception:  # noqa: BLE001 — fall back to the FLAGS default
            bump_counter("serving.replica_hb_interval_fallback")
    if hb_interval is None:
        from ..core.flags import flag

        hb_interval = max(flag("FLAGS_heartbeat_ttl") / 3.0, 0.05)
    hb_store.set(f"{fleet_prefix}/pid/{rank}", str(os.getpid()))
    hb = hb_store.register_heartbeat(rank, hb_interval,
                                     prefix=f"{fleet_prefix}/hb")

    def _term(signum, frame):
        # SIGTERM is a post-mortem moment: dump the flight recorder
        # BEFORE draining so the artifact reflects the serving state the
        # signal interrupted. The dump runs on the daemon thread, NOT in
        # the signal frame: the handler interrupts arbitrary bytecode —
        # possibly _publish_metrics holding a (non-reentrant) registry
        # lock — and a synchronous snapshot here could deadlock the
        # whole shutdown
        def _dump_and_stop():
            telemetry.flight_dump("sigterm", worker=worker, rank=rank)
            server.shutdown(drain=False)

        threading.Thread(target=_dump_and_stop, daemon=True).start()

    with contextlib.suppress(ValueError):  # non-main thread (tests)
        signal.signal(signal.SIGTERM, _term)

    # serve until a shutdown RPC / SIGTERM — or until the fleet master
    # is gone for good: a replica that outlives its control plane must
    # exit (the supervisor owns respawn), not orphan itself heartbeating
    # into the void forever
    def _publish_metrics():
        # the replica's registry snapshot, published at the heartbeat
        # cadence: the router's fleet_metrics() merges these into the
        # one fleet-wide view (TTFT/queue-wait percentiles, tokens/s)
        with contextlib.suppress(Exception):
            hb_store.set(f"{fleet_prefix}/metrics/{rank}",
                         json.dumps(
                             telemetry.registry().snapshot()).encode())

    _publish_metrics()
    rc = 0
    misses = 0
    pub_every = max(hb_interval * 2, 1.0)
    # a TP-group leader polls at the MEMBERSHIP cadence (a member death
    # must surface within ~one membership lease, not one publish
    # cadence); metric publishing keeps its own slower clock
    wait_s = (pub_every if group is None
              else min(pub_every, max(group.interval, 0.05)))
    last_pub = time.monotonic()
    while not server.stopped.wait(wait_s):
        if group is not None:
            try:
                group.check("leader-serve")
            except PeerFailureError as e:
                # the gang is broken: the GROUP dies as one unit — this
                # process stops serving (its fleet heartbeat lapses, so
                # the router sees ONE replica death) and exits for the
                # supervisor to respawn the gang
                telemetry.flight_dump("tp_member_death", worker=worker,
                                      group=group.group_id,
                                      error=str(e))
                bump_counter("tp.group_collapsed")
                logger.error("replica %r: TP gang broken (%s); exiting "
                             "for respawn", worker, e)
                server.shutdown(drain=False)
                rc = 1
                break
        if time.monotonic() - last_pub < pub_every:
            continue
        last_pub = time.monotonic()
        _publish_metrics()
        try:
            hb_store.check(f"{fleet_prefix}/pid/{rank}")
            misses = 0
        except Exception:  # noqa: BLE001 — master unreachable this probe
            misses += 1
            if misses >= 3:
                logger.error(
                    "replica %r lost the fleet master at %s; exiting",
                    worker, master_endpoint)
                bump_counter("serving.replica_master_lost")
                server.shutdown(drain=False)
                rc = 1
                break
    if group is not None:
        if rc == 0:
            # deliberate exit: members must read the leader's silence as
            # a release, not a crash to respawn from
            group.announce_shutdown()
        group.stop()
    _publish_metrics()  # final snapshot: a drained exit still reports
    hb.stop(hb_interval + 1)
    with contextlib.suppress(Exception):
        hb_store.delete_heartbeat(rank, prefix=f"{fleet_prefix}/hb")
    with contextlib.suppress(Exception):
        hb_store.close()
    tdir = os.environ.get(TRACE_DIR_ENV)
    if tdir:
        # this process's half of the cross-process timeline; a SIGKILLed
        # replica never reaches here, which is exactly the gap the
        # survivors' failover spans explain
        with contextlib.suppress(Exception):
            os.makedirs(tdir, exist_ok=True)
            telemetry.export_chrome_trace(os.path.join(
                tdir, f"trace-{worker}-{os.getpid()}.json"))
    # let the dispatcher flush the shutdown call's reply before leaving
    time.sleep(0.2)
    rpc.shutdown()
    return rc
