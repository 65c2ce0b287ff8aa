"""Replica serving fleet: health-gated routing, bit-exact failover,
elastic membership — the tier in front of N ``ServingFrontend`` replicas.

One chip's engine saturates at its slot count; the "millions of users"
architecture is a ROUTER fronting N replicas, built so a replica dying
mid-decode costs one retry, not a lost request:

* **Load-aware dispatch** — each admission is scored against every
  eligible replica's ``health()`` snapshot (queue depth, queued-token
  backlog, in-flight KV slots) and lands on the least-loaded one.
* **Health gating** — a replica is routed around when its router-side
  ``CircuitBreaker`` is open (tripped by failed results or out-of-band
  death evidence), its frontend stopped admitting, or — with a gang
  store — its fleet heartbeat lapsed: a ``PeerFailureDetector``
  (``distributed/gang.py``) sweeping the CURRENT membership marks it
  dead within one ``FLAGS_heartbeat_ttl`` lease.
* **Bit-exact failover** — every engine samples from per-request key
  streams that are a pure function of ``(engine seed, rid, token
  index)``, and the router owns the rid space. A request stranded on a
  failed replica is resubmitted to a healthy one as ``original prompt +
  tokens already emitted`` with ``token_base = len(emitted)`` — the
  continuation is token-identical to the uninterrupted run, whether the
  replay starts from token 0 (replica died, partials unknown) or
  mid-stream (replica retired it ``failed`` with partial output). The
  contract requires every replica to serve the same weights with the
  same engine seed/sampling config (checked at registration, mismatches
  are logged and counted).
* **Hedging** — a tail-latency-sensitive ``submit(hedge=True)`` runs on
  the two best replicas at once; the first terminal result wins and the
  loser is cancelled. Determinism makes the copies token-identical, so
  whichever finishes first is THE answer.
* **Elastic membership** — ``scale_out()`` admits a replica after
  warmup; ``scale_in()`` drains it (``shutdown(drain=True)``: in-flight
  requests finish, queued ones are requeued onto the survivors) before
  deregistering its store presence and heartbeat. Replica processes run
  under the ``launch()`` supervisor with ``restart_policy="worker"``
  (:func:`launch_fleet`): a crashed replica is respawned alone, within
  the supervisor's restart budget, while the survivors keep serving.

The router is a synchronous pump like the frontend: ``submit()`` as
requests arrive, ``step()`` to make progress, ``results(wait=True)`` to
drain. Terminal statuses mirror the frontend's; the retirement switch
(``_RETIREMENT``) is CI-gated to cover every status a replica can emit
(tests/test_no_bare_except.py).

**Durability / hot standby (PR 8).** The router tier itself is no longer
a single point of failure:

* **Write-ahead request journal** (``models/journal.py``, opt-in via
  ``journal=``): every admission is durable before ``submit()`` acks the
  rid, emitted-token progress is checkpointed every K tokens (streamed
  from replica results envelopes), and retirement GC's the record.
  Journal writes batch and flush at step boundaries — bench e4 gates the
  cost < 5% of active processing (``router_journal_overhead_pct``).
* **Leader lease + fencing** (``distributed/gang.py LeaderLease``, via
  ``leader_lease=``): the active router renews a TTL lease whose
  monotonically increasing fencing token rides every envelope to the
  replicas; a ``ServingRouter(standby=True)`` blocks in
  :meth:`take_over` until the lease frees (clean ``shutdown()`` releases
  it — takeover in ~0) or expires (crash — takeover within one lease),
  then replays the journal, re-pins every replica with the new fence
  (the old leader's late writes bounce typed as ``StaleLeaderError`` and
  it stands down instead of double-dispatching), adopts running copies
  whose ``token_base`` sits inside the journaled prefix, and resubmits
  everything else from the last checkpoint — token streams bit-identical
  to the uninterrupted run, by the same per-request key-stream contract
  replica failover rides.
* **Idempotent client surface**: ``submit(rid=...)`` dedups against the
  live request table AND the journal's retired cache, so a client that
  resubmits after a leader change gets the same request (or its cached
  verdict), never a duplicate execution.

**Disaggregated prefill/decode (this PR).** Replicas declare a serving
role (``ServingFrontend(role=...)``: ``prefill`` / ``decode`` /
``both``). When the fleet has both pools, a fresh request runs as two
legs: a one-token prefill on the prefill pool (the engine HOLDS its KV
pages at retirement), then a chunked, CRC-framed, resumable page
transfer (``models/transfer.py``) to a decode replica, which adopts the
pages and produces the rest of the stream — bit-identical to the
colocated run, because the first token is carried over and the decode
leg's key stream continues at index 1 exactly as a colocated second
token would. The failure matrix is typed end to end: source loss at any
point re-prefills on a survivor (``TransferSourceError`` /
``_abandon_transfer``); destination failures charge a bounded transfer
budget (``max_transfer_retries``, exhaustion retires ``failed`` —
never a hang); and a router crash mid-hop is covered by the journal's
HANDOFF record (admit-grade durable BEFORE the decode dispatch acks),
which ``take_over()`` re-drives exactly once via the source's
rid-idempotent export. Roles are ADVISORY: any pool imbalance degrades
requests to colocated serving, never to loss.
"""
from __future__ import annotations

import contextlib
import itertools
import json
import time

import numpy as np

from ..core import perfwatch, telemetry
from ..core.resilience import (
    CircuitBreaker,
    Deadline,
    ServingUnavailable,
    StaleLeaderError,
    TenantQuotaExceeded,
    bump_counter,
    logger,
)
from .frontend import RequestResult, latency_summaries
from .qos import QoSPolicy, tenant_label, tenant_summaries
from .transfer import (
    TransferDestError,
    TransferNoCapacity,
    TransferSourceError,
    transfer_pages,
)

__all__ = ["ServingRouter", "launch_fleet"]

# per-replica membership gauges, exported on every fleet_metrics() call
# so ANY registry snapshot (and every flight dump embedding one) carries
# the fleet view — the data source `obs fleet` renders from a live
# registry, a saved snapshot, or a post-mortem dump alike. Documented in
# README "Observability"; CI-gated against orphaning.
_M_REP_STATE = telemetry.gauge(
    "fleet.replica_state", "per-replica membership state "
    "(1 up / 2 draining / 0 dead)")
_M_REP_BREAKER = telemetry.gauge(
    "fleet.replica_breaker", "router-side breaker state per replica "
    "(0 closed / 1 half-open / 2 open)")
_M_REP_ASSIGNED = telemetry.gauge(
    "fleet.replica_assigned", "requests currently assigned per replica")
_M_REP_SERVED = telemetry.gauge(
    "fleet.replica_served", "requests served per replica")
_M_REP_HB_AGE = telemetry.gauge(
    "fleet.replica_hb_age_s", "age of each replica's last fleet "
    "heartbeat (store-backed fleets only)")
_M_REP_INC = telemetry.gauge(
    "fleet.replica_incarnation", "per-replica incarnation marker: the "
    "{inc=} label carries the replica server's pinned incarnation "
    "prefix (value is always 1)")
_M_REP_ROLE = telemetry.gauge(
    "fleet.replica_role", "per-replica serving role marker: the "
    "{role=} label carries prefill/decode/both (value is always 1)")
_M_XFER_TICKET = telemetry.gauge(
    "fleet.transfer_ticket", "live KV page-transfer tickets, one "
    "labeled point per handoff ({rid=,ticket=,src=}; 1 in flight / "
    "0 resolved)")
_M_XFER_INFLIGHT = telemetry.gauge(
    "fleet.transfer_inflight", "prefill→decode page transfers "
    "currently in flight (awaiting a destination or mid-wire)")

# a call into a replica failing with one of these is REPLICA-level
# evidence (process dead, transport down, server deregistered), not a
# request-level verdict: the router kills the replica and fails over.
# CommTimeoutError is a TimeoutError; InjectedFault a ConnectionError.
_TRANSPORT_ERRORS = (ConnectionError, TimeoutError, ServingUnavailable)


class _Replica:
    """One registered replica: frontend + router-side health state."""

    __slots__ = ("id", "frontend", "breaker", "state", "hb", "assigned",
                 "probes", "served", "h_cache", "h_ts", "p_cache",
                 "role")

    def __init__(self, rep_id, frontend, breaker):
        self.id = rep_id
        self.frontend = frontend
        self.breaker = breaker
        self.state = "up"            # up | draining | dead
        self.role = "both"           # prefill | decode | both (advisory)
        self.hb = None               # store heartbeat handle
        self.assigned: set = set()   # rids currently pending here
        self.probes: set = set()     # rids riding a half-open probe slot
        self.served = 0
        self.h_cache = None          # remote health snapshot + its age
        self.h_ts = 0.0
        self.p_cache = None          # live-progress piggyback (journal)


class _FleetRequest:
    """Router-side record of one client request across failovers."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "priority", "deadline",
                 "emitted", "live", "excluded", "failovers", "hedged",
                 "discard", "deadline_s", "trace", "tenant", "phase",
                 "transfers")

    def __init__(self, rid, prompt, max_new_tokens, priority, deadline,
                 hedged, deadline_s=None, tenant=None):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.priority = int(priority)
        self.deadline = deadline
        self.deadline_s = deadline_s  # original budget (journal replay)
        self.tenant = tenant          # QoS lane, rides every attempt
        # telemetry trace id minted with the request (router-owned, like
        # the rid): every attempt's spans — across replicas, processes
        # and failover hops — stitch under it. Journal replays mint a
        # fresh one (the trace is observability, not request state).
        self.trace = (telemetry.new_trace_id() if telemetry.enabled()
                      else None)
        self.emitted = np.zeros((0,), np.int32)  # tokens delivered by
        #                                          failed/drained attempts
        self.live: set = set()       # replica ids where rid is pending
        self.excluded: set = set()   # replicas this rid must avoid
        # replicas whose NEXT terminal row for this rid is a takeover
        # artifact (a stale copy the new leader cancelled), not a client
        # verdict — swallowed in _collect, which also re-enables the
        # replica for this rid
        self.discard: set = set()
        self.failovers = 0
        self.hedged = bool(hedged)
        # disaggregated prefill/decode: None = colocated (the default
        # and every fallback), "prefill" = the one-token prefill leg is
        # out, "decode" = prefill retired, the KV handoff / decode leg
        # owns the request. Router-volatile — the journal's HANDOFF
        # record (not this field) is what survives a crash.
        self.phase = None
        self.transfers = 0           # failed transfer attempts (budget)


class ServingRouter:
    """Health-gated, failover-capable router over ``ServingFrontend``
    replicas.

    Usage::

        router = ServingRouter(max_failovers=3)
        router.add_replica(make_frontend())     # N times (or scale_out)
        rid = router.submit(prompt, max_new_tokens=64)
        for rid, res in router.results(wait=True).items():
            print(rid, res.status, res.tokens)

    With a gang ``store``, replicas heartbeat under
    ``{fleet_prefix}/hb`` and a ``PeerFailureDetector`` sweeping the
    current membership routes around a silent death within one lease —
    the same machinery a multi-process fleet under ``launch()`` uses.
    """

    def __init__(self, max_failovers=3, hedge=False,
                 default_max_new_tokens=64, token_unit=64,
                 store=None, fleet_prefix="fleet", lease=None,
                 heartbeat_interval=None, breaker_threshold=3,
                 breaker_cooldown_s=30.0, health_ttl=0.05,
                 journal=None, journal_root=None, leader_lease=None,
                 standby=False, qos=None, max_transfer_retries=3):
        from ..core.flags import flag

        self.max_failovers = int(max_failovers)
        # bounded budget for the prefill→decode page-transfer leg: a
        # destination that keeps failing imports charges this, and
        # exhaustion retires the request "failed" — a handoff can
        # degrade or fail, it can never hang
        self.max_transfer_retries = int(max_transfer_retries)
        self.health_ttl = float(health_ttl)  # remote snapshot reuse window
        self.hedge_default = bool(hedge)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.token_unit = float(token_unit)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        # multi-tenant QoS at the CLIENT surface: quota_tokens bounds a
        # tenant's outstanding fleet-wide cost here (typed
        # TenantQuotaExceeded — the one submit surface that raises);
        # the same policy object is usually shared with the replica
        # frontends, whose WFQ weights it also drives. The default has
        # no quotas: tenant-less traffic is unchanged.
        self.qos = qos if qos is not None else QoSPolicy()
        self._tenant_out: dict = {}   # tenant -> outstanding token cost
        # autoscaler (models/autoscale.py), attached via
        # attach_autoscaler(): its control loop rides step()
        self._autoscaler = None
        self._replicas: dict[int, _Replica] = {}
        self._requests: dict[int, _FleetRequest] = {}
        self._results: dict[int, RequestResult] = {}
        self._parked: list[int] = []
        # live prefill→decode handoffs: rid -> {"ticket", "source"}.
        # An entry exists from export (HANDOFF journaled) until the
        # decode leg dispatches (handoff_done) or the hop is abandoned.
        self._transfers: dict[int, dict] = {}
        self._rids = itertools.count()
        self._rep_ids = itertools.count()
        self._engine_fingerprint = None
        # fleet store (optional): membership keys + replica heartbeats +
        # the lease-based failure detector
        self._store = store
        self._prefix = fleet_prefix
        self._lease = float(lease if lease is not None
                            else flag("FLAGS_heartbeat_ttl"))
        self._hb_interval = float(heartbeat_interval if heartbeat_interval
                                  is not None else max(self._lease / 3, 0.05))
        self._detector = None
        if store is not None:
            from ..distributed.gang import GangContext, PeerFailureDetector

            # publish the beat cadence replica PROCESSES must honor:
            # they beat for themselves (a router-side beat would mask
            # their death), and an interval derived from their own local
            # FLAGS default could exceed this router's lease — replicas
            # would flap dead while perfectly alive (replica_main reads
            # this key before starting its heartbeat). Only the LEADER
            # publishes: a hot standby constructed with a different
            # cadence must not re-pace the live fleet out from under it
            if not standby:
                store.set(f"{fleet_prefix}/hb_interval",
                          repr(self._hb_interval))
            ctx = GangContext(store, rank=-1, world_size=0)
            self._detector = PeerFailureDetector(
                ctx, lease=self._lease, interval=self._hb_interval,
                prefix=f"{fleet_prefix}/hb",
                ranks=self._member_ids).start(beat=False)
        # dispatch-overhead accounting: router bookkeeping vs time inside
        # replica frontends (the acceptance gate records
        # fleet_router_overhead_pct = route_s / wall)
        self._route_s = 0.0
        self._pump_s = 0.0
        # RPC accounting absorbed from remote replicas that left the
        # fleet (scale-in, death, shutdown) so stats() keeps the totals
        self._rpc_retired = {"rpc_s": 0.0, "remote_exec_s": 0.0,
                             "calls": 0}
        self._counts: dict[str, int] = {}
        self._t0 = time.monotonic()
        # fleet-metrics state: last merged snapshot (stats() latency
        # summaries read it) and the previous (tokens_total, ts) pair
        # the fleet tokens/s rate is computed over
        self._last_fleet = None
        self._fm_prev = None
        # fleet-level SLO monitor (perfwatch): evaluates the declared
        # objectives over the MERGED histograms (router + every
        # replica's store-published snapshot), so the burn rate is the
        # fleet's, not one process's — built lazily at first
        # fleet_metrics() call
        self._slo_fleet = None
        # ---- durability / hot standby (see module docstring)
        self._journal = journal
        self._journal_root = journal_root
        self._llease = leader_lease
        self._standby = bool(standby)
        self._deposed = False
        if leader_lease is not None and not standby:
            # the ACTIVE router must hold the lease before serving; a
            # held-by-other lease here is a deployment error (two actives)
            if not leader_lease.wait_acquire(
                    timeout=leader_lease.ttl * 2):
                raise RuntimeError(
                    f"leader lease {leader_lease.key!r} is held by a "
                    "live leader; start this router with standby=True")
        if (self._journal is None and journal_root is not None
                and not standby):
            from .journal import RequestJournal

            # RECOVER, not create: a restart-in-place over an existing
            # journal root must finish what the previous incarnation
            # admitted (the durable-before-ack promise survives the
            # restart) — and must never re-issue a journaled rid
            self._journal = RequestJournal.recover(
                root=journal_root,
                epoch=(leader_lease.fence if leader_lease is not None
                       and leader_lease.fence is not None else 0),
                store=store, prefix=fleet_prefix)
        if self._journal is not None and not standby:
            # adopt whatever live state the journal brought (empty for a
            # fresh root): requests park until replicas register
            n, _, _ = self._restore_requests({})
            if n:
                logger.warning(
                    "journal restart-in-place: %d unfinished request(s) "
                    "recovered; they re-dispatch as replicas register",
                    n)

    # -------------------------------------------------------- membership

    def _member_ids(self):
        return [r.id for r in self._replicas.values() if r.state == "up"]

    def _fingerprint(self, frontend):
        return tuple(frontend.fingerprint())

    def add_replica(self, frontend, replica_id=None, warmup=False):
        """Register a replica (its frontend must already be started) —
        a local ``ServingFrontend`` or a ``RemoteFrontend`` stub for a
        replica process, interchangeably. Returns the replica id. With a
        fleet store, the replica's membership key is published and its
        heartbeat starts (remote replicas beat for THEMSELVES from their
        own process — a router-side beat would mask their death) —
        silent death is then detected by lease, not by a failed
        dispatch. Re-using the id of a DEAD replica replaces the corpse:
        that is how a supervisor-respawned replica process rejoins."""
        rep_id = (next(self._rep_ids) if replica_id is None
                  else int(replica_id))
        while replica_id is None and rep_id in self._replicas:
            rep_id = next(self._rep_ids)
        prev = self._replicas.get(rep_id)
        if prev is not None:
            if prev.state != "dead":
                raise ValueError(f"replica id {rep_id} already registered")
            self._absorb_rpc_stats(prev)
            del self._replicas[rep_id]
        fp = self._fingerprint(frontend)
        if self._engine_fingerprint is None:
            self._engine_fingerprint = fp
        elif fp != self._engine_fingerprint:
            # a mismatched seed/sampling config silently breaks the
            # bit-exact failover contract — loud, counted, but admitted
            # (the operator may be doing a deliberate config rollout)
            bump_counter("fleet.config_mismatch")
            logger.warning(
                "replica %d engine config %r differs from the fleet's %r; "
                "failover replays will NOT be bit-exact", rep_id, fp,
                self._engine_fingerprint)
        if warmup:
            frontend.warmup()
        if (self._llease is not None and self._llease.fence is not None
                and hasattr(frontend, "set_fence")):
            # every envelope to this replica now carries our fencing
            # token; a deposed predecessor's late writes bounce typed
            frontend.set_fence(self._llease.fence)
        if self._journal is not None and hasattr(frontend,
                                                 "want_progress"):
            # journaling routers want the live-progress piggyback on
            # every results envelope (PROGRESS checkpoints ride it)
            frontend.want_progress = True
        rep = _Replica(rep_id, frontend, CircuitBreaker(
            f"fleet.replica.{rep_id}",
            failure_threshold=self.breaker_threshold,
            cooldown_s=self.breaker_cooldown_s))
        # learn the replica's declared serving role (prefill / decode /
        # both) from its health surface. ADVISORY: the candidate filter
        # prefers matching roles but never excludes on it, so a role
        # mismatch degrades to colocated serving, never to loss — and a
        # frontend predating the role field registers as "both".
        with contextlib.suppress(Exception):
            role = (frontend.health() or {}).get("role")
            if role in ("prefill", "decode", "both"):
                rep.role = role
        if self._store is not None:
            self._store.set(f"{self._prefix}/member/{rep_id}", b"up")
            if not getattr(frontend, "is_remote", False):
                rep.hb = self._store.register_heartbeat(
                    rep_id, self._hb_interval, prefix=f"{self._prefix}/hb")
        self._replicas[rep_id] = rep
        bump_counter("fleet.replica_up")
        self._publish_members()
        self._route_parked()
        return rep_id

    def _publish_members(self):
        """Publish the CURRENT membership (with each remote replica's
        RPC address) so a hot standby can rebuild its stubs at takeover
        without configuration. Only the leader writes it."""
        if self._store is None or self._deposed or self._standby:
            return
        members = {}
        for rep in self._replicas.values():
            if rep.state == "dead":
                continue
            fe = rep.frontend
            if getattr(fe, "is_remote", False):
                members[str(rep.id)] = {"worker": fe.worker,
                                        "server": fe.server}
            else:
                members[str(rep.id)] = None  # in-process: not adoptable
        with contextlib.suppress(Exception):
            self._store.set(f"{self._prefix}/members",
                            json.dumps(members).encode())

    def scale_out(self, frontend, replica_id=None, warmup=True):
        """Grow the fleet: warm the replica's compiled shapes FIRST (a
        cold replica would absorb compile time into live requests), then
        admit it and immediately route parked/backlogged work there."""
        bump_counter("fleet.scale_out")
        return self.add_replica(frontend, replica_id=replica_id,
                                warmup=warmup)

    def scale_in(self, replica_id):
        """Shrink the fleet gracefully: stop routing to the replica,
        drain it (in-flight requests FINISH and deliver normally; queued
        ones are requeued onto the survivors with their budgets intact),
        then deregister its membership and heartbeat."""
        rep = self._replicas[replica_id]
        rep.state = "draining"
        bump_counter("fleet.scale_in")
        try:
            rep.frontend.shutdown(drain=True)
        except _TRANSPORT_ERRORS as e:
            # an unreachable replica cannot drain: this scale-in is a
            # death — fail over its stranded requests instead of raising
            # out of the removal with the corpse still registered
            self._kill_replica(rep, f"scale_in drain failed: {e!r}")
        else:
            self._collect(rep)
            self._deregister(rep)
        self._absorb_rpc_stats(rep)
        if telemetry.enabled():
            self._retire_replica_gauges(rep)
        del self._replicas[replica_id]
        self._publish_members()
        self._route_parked()

    @staticmethod
    def _fold_rpc_stats(acc, frontend):
        """Accumulate one remote frontend's transport accounting into
        ``acc`` — the single definition of which keys make up the
        ``fleet_rpc_overhead_pct`` inputs."""
        if getattr(frontend, "is_remote", False):
            with contextlib.suppress(Exception):
                s = frontend.stats()
                acc["rpc_s"] += s.get("rpc_s", 0.0)
                acc["remote_exec_s"] += s.get("remote_exec_s", 0.0)
                acc["calls"] += s.get("calls", 0)

    def _absorb_rpc_stats(self, rep):
        """Keep a departing remote replica's transport accounting in the
        router's running totals (the bench overhead gate reads them
        after the fleet has churned)."""
        self._fold_rpc_stats(self._rpc_retired, rep.frontend)

    def _deregister(self, rep):
        if rep.hb is not None:
            with contextlib.suppress(Exception):
                rep.hb.stop(self._hb_interval + 1)
            rep.hb = None
        if self._store is not None:
            # membership + beat keys must not linger: a deliberate leave
            # is not a death, and the next sweep must not see a stale beat
            with contextlib.suppress(Exception):
                self._store.delete_key(f"{self._prefix}/member/{rep.id}")
            with contextlib.suppress(Exception):
                self._store.delete_heartbeat(rep.id,
                                             prefix=f"{self._prefix}/hb")

    def fail_replica(self, replica_id, reason="operator kill"):
        """Declare a replica dead NOW (fault drills / orchestrator
        signal): trip its breaker, deregister it, and fail over every
        request stranded there."""
        rep = self._replicas.get(replica_id)
        if rep is not None:
            self._kill_replica(rep, reason)

    def _kill_replica(self, rep, reason):
        # ONE death per replica, however many signals report it (lease
        # sweep, transport errors on submit/collect/cancel, operator
        # fail_replica) and however many member PROCESSES back the
        # replica — a TP gang (models/tp_serving.py) registers as one
        # replica id, so a group collapse is one breaker trip, one
        # replica_dead flight event, and one failover charge per
        # stranded rid, not one per member (regression-pinned in
        # tests/test_tp_serving.py)
        if rep.state == "dead":
            return
        rep.state = "dead"
        # the event rides the ring BEFORE the breaker trip dumps it, so
        # the post-mortem file names the dead replica and why
        telemetry.flight_recorder().record(
            "replica_dead", replica=rep.id, reason=str(reason),
            stranded=sorted(rep.assigned))
        rep.breaker.trip()
        bump_counter("fleet.replica_dead")
        logger.warning("replica %d marked dead (%s); failing over %d "
                       "stranded request(s)", rep.id, reason,
                       len(rep.assigned))
        # salvage results the replica already retired before it broke —
        # a terminal verdict that exists must not be recomputed. Short
        # per-call budget: a dead replica PROCESS can't answer, and the
        # salvage must not stall failover for the full rpc timeout.
        with contextlib.suppress(Exception):
            self._collect(rep, timeout=2.0)
        self._deregister(rep)
        self._publish_members()
        for rid in list(rep.assigned):
            rep.assigned.discard(rid)
            freq = self._requests.get(rid)
            if freq is None:
                continue
            freq.live.discard(rep.id)
            freq.excluded.add(rep.id)
            if freq.live:
                continue  # a hedge copy is still running elsewhere
            self._failover(freq, None, f"replica {rep.id} dead: {reason}")
        # the SAME pass sweeps requests mid-handoff: a rid whose page
        # transfer sources from this replica is no longer in
        # rep.assigned (its prefill leg already retired), so the loop
        # above never sees it — without this sweep a ticket in flight
        # would strand its request until the transfer's own wire error
        # surfaced, or forever if no transfer attempt was running
        for rid, xfer in list(self._transfers.items()):
            if xfer["source"] != rep.id:
                continue
            freq = self._requests.get(rid)
            if freq is None:
                self._clear_transfer(rid)
                continue
            self._abandon_transfer(
                freq, f"source replica {rep.id} dead: {reason}")

    # --------------------------------------------------------- dispatch

    def _score(self, h):
        """Load score from one health snapshot — lower is better. The
        three load signals share a scale by normalizing the token
        backlog to ``token_unit`` (≈ one request's decode budget)."""
        return (h["queue_depth"] + h["active_slots"]
                + h["queued_tokens"] / self.token_unit)

    def _accept_health(self, rep, snap):
        """Install a health snapshot unless it is provably STALER than
        the one cached: snapshots are stamped with the sender's
        monotonic clock + incarnation (models/remote.py), so two from
        the same incarnation order by sender time — a delayed results
        envelope's piggyback can no longer out-vote a fresher direct
        probe just by arriving later. Returns the now-current cache."""
        if snap is not None:
            cur = rep.h_cache
            ts, inc = snap.get("_ts"), snap.get("_inc")
            if (cur is not None and ts is not None
                    and inc is not None and cur.get("_inc") == inc
                    and cur.get("_ts") is not None
                    and ts < cur["_ts"]):
                bump_counter("fleet.stale_health_dropped")
            else:
                rep.h_cache, rep.h_ts = snap, time.monotonic()
        return rep.h_cache

    def _disagg_active(self) -> bool:
        """Disaggregated prefill/decode serving is on iff at least one
        up replica declared role=prefill AND at least one up replica
        can decode (role decode/both). Evaluated per admission, so a
        pool that loses its last prefill (or decode) replica degrades
        NEW requests to colocated serving instead of wedging them."""
        has_prefill = has_decode = False
        for rep in self._replicas.values():
            if rep.state != "up":
                continue
            if rep.role == "prefill":
                has_prefill = True
            if rep.role in ("decode", "both"):
                has_decode = True
        return has_prefill and has_decode

    def _candidates(self, freq):
        """Eligible replicas for this request, best (least loaded)
        first. Closed-breaker replicas are preferred; half-open ones are
        used only when no closed one is eligible, and routing there
        consumes the breaker's probe slot (the request IS the probe).

        A disaggregated request's phase steers the pool: the prefill
        leg prefers role prefill/both replicas, everything else (decode
        legs AND colocated requests) prefers decode/both. The steer is
        a sort preference, not a filter — when no matching-role replica
        is eligible the request lands on whatever is, degrading to
        colocated serving rather than starving."""
        want = (("prefill", "both") if freq.phase == "prefill"
                else ("decode", "both"))
        closed, half_open = [], []
        for rep in list(self._replicas.values()):
            if rep.state != "up" or rep.id in freq.excluded:
                continue
            if rep.id in freq.live:
                # a copy of this rid is already pending there (hedge arm
                # or a not-yet-collected attempt) — resubmitting the same
                # rid to that frontend would raise
                continue
            state = rep.breaker.state()
            if state == CircuitBreaker.OPEN:
                continue
            t0 = time.monotonic()
            try:
                # remote probes cost a wire round-trip per call, and the
                # server already answers from a snapshot refreshed at its
                # own pump-turn boundaries — a router-side TTL adds no
                # staleness the wire didn't already imply. Local
                # frontends stay uncached (health() is cheap and tests
                # preload replicas directly between dispatches).
                if (rep.h_cache is not None
                        and getattr(rep.frontend, "is_remote", False)
                        and t0 - rep.h_ts < self.health_ttl):
                    h = rep.h_cache
                else:
                    h = self._accept_health(rep, rep.frontend.health())
                self._pump_s += time.monotonic() - t0
            except StaleLeaderError as e:  # deposed: the replica is
                # fine, WE are not the leader anymore
                self._pump_s += time.monotonic() - t0
                self._stand_down(str(e))
                return []
            except Exception as e:  # a broken health probe is a death
                self._pump_s += time.monotonic() - t0
                self._kill_replica(rep, f"health() raised: {e!r}")
                continue
            if not h["ready"]:
                continue
            (closed if state == CircuitBreaker.CLOSED
             else half_open).append(
                 ((rep.role not in want, self._score(h)), rep.id))
        pool = sorted(closed) or sorted(half_open)
        return pool

    def _submit_to(self, freq, rep_id, kv_import=None):
        rep = self._replicas[rep_id]
        if rep.state != "up":
            # a candidate killed mid-dispatch (transport error on an
            # earlier submit in this same pool walk)
            return False
        probe = rep.breaker.state() == CircuitBreaker.HALF_OPEN
        if probe and not rep.breaker.allow():
            return False
        k = len(freq.emitted)
        if freq.phase == "prefill":
            # the PREFILL leg: full prompt, exactly one token, and the
            # engine holds the request's KV pages for export at retire
            # instead of recycling them
            args = (freq.prompt, 1)
            extra = {"token_base": 0, "hold_kv": True}
        elif kv_import is not None:
            # the DECODE leg of a completed handoff: the full budget
            # from token 0, seeded by the imported pages — the engine
            # adopts them and skips the prefill pass entirely
            args = (freq.prompt, freq.max_new_tokens)
            extra = {"token_base": 0, "kv_import": kv_import}
        else:
            prompt = (np.concatenate([freq.prompt, freq.emitted])
                      if k else freq.prompt)
            args = (prompt, freq.max_new_tokens - k)
            extra = {"token_base": k}
        t0 = time.monotonic()
        try:
            rep.frontend.submit(args[0], args[1],
                                priority=freq.priority,
                                deadline_s=freq.deadline, rid=freq.rid,
                                trace=freq.trace,
                                tenant=freq.tenant, **extra)
            self._pump_s += time.monotonic() - t0
        except StaleLeaderError as e:
            self._pump_s += time.monotonic() - t0
            if probe:
                rep.breaker.release_probe()
            self._stand_down(str(e))
            return False
        except _TRANSPORT_ERRORS as e:
            self._pump_s += time.monotonic() - t0
            # the per-call timeout / resend budget is the router-side
            # evidence a replica PROCESS is gone; the dispatch falls
            # through to the next candidate
            if probe:
                rep.breaker.release_probe()
            self._kill_replica(rep, f"submit transport error: {e!r}")
            return False
        rep.assigned.add(freq.rid)
        freq.live.add(rep_id)
        if probe:
            rep.probes.add(freq.rid)
        if telemetry.enabled():
            # the hop record a stitched timeline reads the request's
            # replica placement (and failover path) off
            telemetry.trace_event("fleet.dispatch", trace=freq.trace,
                                  rid=freq.rid, replica=rep_id,
                                  token_base=extra["token_base"],
                                  phase=freq.phase)
        return True

    def _dispatch(self, freq):
        pool = self._candidates(freq)
        sent = False
        for _, rep_id in pool:
            if self._submit_to(freq, rep_id):
                sent = True
                break
        if sent and freq.hedged:
            for _, rep_id in pool:
                if rep_id not in freq.live and self._submit_to(freq,
                                                               rep_id):
                    bump_counter("fleet.hedged")
                    if telemetry.enabled():
                        telemetry.trace_event("fleet.hedge",
                                              trace=freq.trace,
                                              rid=freq.rid,
                                              replica=rep_id)
                    break
        return sent

    def _failover(self, freq, partial_tokens, reason, charge=True):
        """Resubmit a stranded request. ``partial_tokens`` (if the failed
        attempt surfaced any) extend the emitted prefix so the replay
        resumes mid-stream instead of recomputing; determinism makes the
        continuation bit-identical either way."""
        if partial_tokens is not None and len(partial_tokens):
            freq.emitted = np.concatenate(
                [freq.emitted, np.asarray(partial_tokens, np.int32)])
        if len(freq.emitted) >= freq.max_new_tokens:
            # the failed attempt had in fact finished the budget — the
            # emitted prefix IS the answer
            self._deliver(freq, "ok", freq.emitted, reason)
            return
        if charge:
            freq.failovers += 1
        if freq.failovers > self.max_failovers:
            bump_counter("fleet.failover_budget_exhausted")
            self._deliver(freq, "failed", freq.emitted,
                          f"failover budget exhausted ({reason})")
            return
        bump_counter("fleet.failover")
        if telemetry.enabled():
            telemetry.trace_event("fleet.failover", trace=freq.trace,
                                  rid=freq.rid, reason=str(reason),
                                  emitted=len(freq.emitted))
        telemetry.flight_recorder().record("failover", rid=freq.rid,
                                           reason=str(reason))
        if not self._dispatch(freq):
            if freq.rid not in self._parked:
                self._parked.append(freq.rid)

    def _route_parked(self):
        for rid in list(self._parked):
            freq = self._requests.get(rid)
            if freq is None:
                with contextlib.suppress(ValueError):
                    self._parked.remove(rid)
                continue
            if freq.deadline.expired():
                self._deliver(freq, "timed_out", freq.emitted,
                              "expired while parked at the router")
                continue
            if self._dispatch(freq):
                self._parked.remove(rid)
                continue
            ups = [r for r in self._replicas.values() if r.state == "up"]
            if ups and all(r.id in freq.excluded for r in ups):
                # every live replica already failed this request
                self._deliver(freq, "failed", freq.emitted,
                              "every live replica excluded by failover")

    # ------------------------------------------------------ client API

    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_s=None, hedge=None, rid=None,
               tenant=None) -> int:
        """Admit one request to the fleet; returns its rid. The verdict
        lands in ``results()``. ``hedge=True`` (or the router-wide
        default) duplicates the request onto the two least-loaded
        replicas; the first terminal result wins.

        ``tenant`` selects the QoS lane: it rides every attempt to the
        replica frontends (WFQ weight, per-tenant metrics), and the
        router enforces the tenant's fleet-wide ``quota_tokens`` HERE —
        an over-quota admission raises the typed
        :class:`TenantQuotaExceeded` (the one submit surface that
        raises; clients back off on it instead of retrying blind).

        ``rid`` is the IDEMPOTENT client surface: a client that owns its
        request ids can resubmit after a leader change and get the SAME
        request — a rid still pending here (or replayed from the
        journal) acks without duplicating, and a recently retired rid
        re-delivers its journaled verdict instead of re-executing."""
        if rid is not None:
            rid = int(rid)
            if rid in self._requests or rid in self._results:
                bump_counter("fleet.dup_submit")
                return rid
            if self._journal is not None:
                cached = self._journal.retired_result(rid)
                if cached is not None:
                    bump_counter("fleet.dup_submit")
                    status, tokens, reason = cached
                    self._results[rid] = RequestResult(rid, status,
                                                       tokens, reason)
                    return rid
            # keep auto rids strictly above explicit ones (no aliasing)
            self._rids = itertools.count(max(rid + 1, next(self._rids)))
        else:
            rid = next(self._rids)
        prompt = np.asarray(prompt).astype(np.int32).ravel()
        max_new = (self.default_max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        # tenant token-budget quota, BEFORE the journal sees the admit:
        # an over-quota request must not become durable state the
        # standby would replay
        cost = int(prompt.size) + max_new
        held = self._tenant_out.get(tenant, 0)
        if not self.qos.check_quota(tenant, held, cost):
            bump_counter("serving.quota_rejected")
            if telemetry.enabled():
                telemetry.counter("serving.quota_rejected").inc(
                    tenant=tenant_label(tenant))
            raise TenantQuotaExceeded(
                f"tenant {tenant_label(tenant)} over quota: {held} "
                f"outstanding + {cost} > "
                f"{self.qos.quota_tokens(tenant)} tokens",
                tenant=tenant)
        # leadership is re-checked at ADMISSION, not just in step(): a
        # leader whose lease lapsed mid-partition (renewal thread stood
        # down, no step() since) must not ack an ADMIT into a journal
        # epoch the new leader has already recovered past — an acked rid
        # nobody will ever serve. held() is an in-memory flag; this
        # costs no store round-trip.
        self._check_leadership()
        if self._standby or self._deposed:
            # not the leader: admitting here would double-serve against
            # the journal's owner — the client must talk to the leader
            bump_counter("fleet.not_leader_rejected")
            self._results[rid] = RequestResult(
                rid, "unavailable", None,
                "this router is not the fleet leader")
            return rid
        deadline = (deadline_s if isinstance(deadline_s, Deadline)
                    else Deadline(deadline_s))
        freq = _FleetRequest(rid, prompt, max_new, priority, deadline,
                             self.hedge_default if hedge is None else hedge,
                             deadline_s=(None if isinstance(deadline_s,
                                                            Deadline)
                                         else deadline_s),
                             tenant=tenant)
        self._requests[rid] = freq
        self._tenant_out[tenant] = held + cost
        if (not freq.hedged and max_new > 1 and self._disagg_active()):
            # disaggregated flow: the first leg is a one-token prefill
            # on the prefill pool; the KV pages hand off to a decode
            # replica at its retirement. Hedged requests stay colocated
            # (two prefill arms would race one another's handoff), as
            # do single-token requests (there is nothing to decode).
            freq.phase = "prefill"
        t0 = time.monotonic()
        pump0 = self._pump_s  # frontend.submit time lands in pump_s
        if self._journal is not None:
            # durable BEFORE the rid is acked: a router crash after this
            # point can lose the process, not the request
            self._journal.admit(rid, prompt, max_new,
                                priority=freq.priority,
                                deadline_s=freq.deadline_s,
                                hedge=freq.hedged, tenant=freq.tenant)
            self._journal.flush()
        if not self._dispatch(freq):
            self._parked.append(rid)
            bump_counter("fleet.parked")
        self._route_s += ((time.monotonic() - t0)
                          - (self._pump_s - pump0))
        return rid

    def cancel(self, rid) -> bool:
        """Cancel a request wherever it lives (parked or on replicas).
        Partial tokens an in-flight copy already produced are preserved
        in the delivered result (same contract as
        ``ServingFrontend.cancel``)."""
        freq = self._requests.get(rid)
        if freq is None:
            return False
        for rep_id in list(freq.live):
            rep = self._replicas.get(rep_id)
            if rep is None or rep.state != "up":
                continue
            # frontend.cancel records a "cancelled" result carrying the
            # partial tokens; collecting it routes through the normal
            # retirement switch, which delivers emitted + partials
            try:
                rep.frontend.cancel(rid)
            except StaleLeaderError as e:
                self._stand_down(str(e))
                return False  # the new leader owns the request now
            except _TRANSPORT_ERRORS as e:
                self._kill_replica(rep, f"cancel transport error: {e!r}")
                if rid not in self._requests:
                    return True  # the kill's failover resolved it
                continue
            except Exception:  # noqa: BLE001 — replica-local refusal
                bump_counter("fleet.cancel_error")
            self._collect(rep)
            if rid not in self._requests:
                return True
        self._deliver(freq, "cancelled", freq.emitted,
                      "cancelled by caller")
        return True

    def pending(self) -> int:
        return len(self._requests)

    def step(self):
        """One fleet turn: sweep liveness (lease-based death detection),
        route parked work, pump every live replica one scheduler turn,
        run the retirement switch over everything that finished, and
        land the journal's batched records."""
        if not self._check_leadership():
            return
        if self._autoscaler is not None:
            # OUTSIDE the route_s window: the autoscaler's decision loop
            # has its own overhead accounting (autoscale_overhead_pct,
            # gated < 3% in bench e7), and a scale-out's warmup is
            # useful work, not routing overhead
            self._autoscaler.maybe_step()
        t_start = time.monotonic()
        pump0 = self._pump_s  # every frontend call below adds to pump_s
        self._sweep_liveness()
        self._route_parked()
        self._pump_transfers()
        for rep in list(self._replicas.values()):
            if rep.state != "up":
                continue
            t0 = time.monotonic()
            try:
                if not getattr(rep.frontend, "is_remote", False):
                    # remote replicas pump THEMSELVES (ReplicaServer's
                    # pump thread); the router's turn is just the
                    # results fetch below
                    if (rep.frontend.pending()
                            or rep.frontend.engine.has_work()):
                        rep.frontend.step()
            except Exception as e:  # replica broke mid-dispatch
                self._pump_s += time.monotonic() - t0
                self._kill_replica(rep, f"step() raised: {e!r}")
                continue
            self._pump_s += time.monotonic() - t0
            self._collect(rep)
            if self._deposed:
                return  # a fenced rejection mid-turn: stop immediately
        self._route_parked()
        self._journal_progress()
        self._route_s += ((time.monotonic() - t_start)
                          - (self._pump_s - pump0))

    def _check_leadership(self) -> bool:
        """False once this router is deposed (its lease lapsed, was
        superseded, or a replica fenced it off) — it stops dispatching;
        the new leader owns every pending request via the journal."""
        if (not self._deposed and self._llease is not None
                and not self._standby and not self._llease.held()):
            self._stand_down("leader lease lost (expired or superseded)")
        return not self._deposed

    def _stand_down(self, reason):
        if self._deposed:
            return
        self._deposed = True
        bump_counter("fleet.deposed")
        logger.warning(
            "router standing down (%s); %d pending request(s) belong to "
            "the new leader via the journal", reason,
            len(self._requests))
        # a deposed leader is a post-mortem moment (StaleLeaderError
        # fencing rejection or a lapsed lease): leave the artifact
        telemetry.flight_dump("stand_down", detail=str(reason),
                              pending=len(self._requests))
        if self._llease is not None:
            self._llease.stand_down()
        if self._journal is not None:
            # a later re-promotion (take_over) recovers from disk under
            # a fresh fence; keep the root, drop the closed handle
            self._journal_root = self._journal.root
            with contextlib.suppress(Exception):
                self._journal.flush()
                self._journal.close()
            self._journal = None

    def _journal_progress(self):
        """Checkpoint emitted-token progress (journal PROGRESS records,
        every K tokens per rid) from the freshest per-replica progress
        view — streamed piggyback for remote replicas, a direct
        ``progress()`` call for local ones — then flush the step's
        batched records."""
        if self._journal is None:
            return
        for rep in self._replicas.values():
            if rep.state != "up":
                continue
            if getattr(rep.frontend, "is_remote", False):
                prog, rep.p_cache = rep.p_cache, None
            else:
                try:
                    prog = rep.frontend.progress()
                except Exception:  # noqa: BLE001 — progress is an
                    # optimization; the admit record alone stays correct
                    bump_counter("fleet.progress_error")
                    continue
            if not prog:
                continue
            for rid, (base, toks) in prog.items():
                freq = self._requests.get(rid)
                if freq is None or not len(toks):
                    continue
                if base > len(freq.emitted) or rid not in rep.assigned:
                    continue  # resumed past a lost checkpoint / stale
                # anchor at the attempt's stream offset: an ADOPTED
                # takeover copy runs with base BELOW the journaled
                # prefix (concat would duplicate); the known prefix up
                # to base + the attempt's tokens is the true stream,
                # journaled only when it actually grows
                merged = (np.concatenate([freq.emitted[:base], toks])
                          if base else toks)
                self._journal.progress(rid, merged)
        self._journal.flush()

    def results(self, wait=False, timeout_s=None) -> dict:
        """Pop terminal results as ``{rid: RequestResult}``. With
        ``wait=True`` the router pumps until every pending request
        resolves, the fleet has no live replica left (remaining requests
        deliver ``unavailable``), or ``timeout_s`` expires (remaining
        deliver ``timed_out``)."""
        if wait:
            deadline = Deadline(timeout_s)
            while self._requests:
                if self._deposed:
                    # the new leader owns the pending requests (journal);
                    # deliver only what already resolved here
                    break
                if not any(r.state == "up"
                           for r in self._replicas.values()):
                    for freq in list(self._requests.values()):
                        self._deliver(freq, "unavailable", freq.emitted,
                                      "no live replica")
                    break
                if deadline.expired():
                    for freq in list(self._requests.values()):
                        self._deliver(freq, "timed_out", freq.emitted,
                                      "results(wait) timeout")
                    break
                self.step()
        out, self._results = self._results, {}
        return out

    # ------------------------------------------------------- retirement

    # status -> handler; CI-gated (tests/test_no_bare_except.py) to cover
    # every terminal state a frontend result can carry, so a new engine
    # status cannot silently fall through the switch
    _RETIREMENT = {
        "ok": "_retire_ok",
        "failed": "_retire_failed",
        "timed_out": "_retire_timed_out",
        "cancelled": "_retire_cancelled",
        "rejected": "_retire_rejected",
        "unavailable": "_retire_unavailable",
    }

    def _collect(self, rep, timeout=None):
        t0 = time.monotonic()
        try:
            fetched = rep.frontend.results(timeout=timeout)
        except StaleLeaderError as e:
            self._pump_s += time.monotonic() - t0
            self._stand_down(str(e))
            return
        except _TRANSPORT_ERRORS as e:
            self._pump_s += time.monotonic() - t0
            self._kill_replica(rep, f"results transport error: {e!r}")
            return
        self._pump_s += time.monotonic() - t0
        # a remote results envelope carries the replica's health snapshot
        # (and live progress, for the journal) for free — refresh the
        # caches without spending separate wire round-trips
        self._accept_health(rep,
                            getattr(rep.frontend, "piggyback_health",
                                    None))
        prog = getattr(rep.frontend, "piggyback_progress", None)
        if prog is not None:
            rep.p_cache = prog
        for rid, res in fetched.items():
            rep.assigned.discard(rid)
            rep.probes.discard(rid)
            freq = self._requests.get(rid)
            if freq is None:
                continue  # already delivered (hedge loser, late cancel)
            freq.live.discard(rep.id)
            if rep.id in freq.discard:
                # a takeover artifact: the new leader cancelled this
                # stale copy (its token_base outran the journaled
                # prefix); the row is not a client verdict. The replica
                # is re-eligible for the rid once the row is consumed.
                freq.discard.discard(rep.id)
                freq.excluded.discard(rep.id)
                if (not freq.live and rid in self._requests
                        and rid not in self._parked):
                    self._failover(freq, None,
                                   "stale takeover copy discarded",
                                   charge=False)
                continue
            handler = self._RETIREMENT.get(res.status)
            if handler is None:
                # unreachable when the CI guard holds; deliver verbatim
                # rather than dropping the request on the floor
                bump_counter("fleet.unknown_terminal")
                self._deliver(freq, res.status, res.tokens, res.reason)
                continue
            getattr(self, handler)(rep, freq, res)

    def _note_verdict(self, rep, rid, ok):
        if ok:
            rep.breaker.record_success()
        else:
            rep.breaker.record_failure()
        rep.probes.discard(rid)

    def _combine(self, freq, res):
        """Full token stream for a terminal attempt: the known emitted
        prefix up to the attempt's ``token_base`` + the attempt's own
        tokens. ``None`` when the attempt resumed PAST the known prefix
        (a journaled checkpoint was lost): the gap tokens are
        unrecoverable from this result, so the caller must replay from
        the prefix instead — determinism regenerates them exactly."""
        base = int(getattr(res, "token_base", 0) or 0)
        if base > len(freq.emitted):
            bump_counter("fleet.progress_gap")
            return None
        if base == 0:
            return res.tokens
        return np.concatenate([freq.emitted[:base], res.tokens])

    def _retire_ok(self, rep, freq, res):
        self._note_verdict(rep, freq.rid, ok=True)
        rep.served += 1
        if freq.phase == "prefill":
            # not a client verdict: the one-token prefill leg finished
            # and the replica is holding its KV pages — begin the hop
            self._begin_handoff(rep, freq, res)
            return
        tokens = self._combine(freq, res)
        if tokens is None:
            self._failover(freq, None,
                           f"replica {rep.id} finished past the known "
                           "prefix (lost checkpoint); replaying",
                           charge=False)
            return
        self._deliver(freq, "ok", tokens, res.reason)

    def _extend_emitted(self, freq, res):
        """Grow the known emitted prefix with an attempt's partial
        tokens, anchored at the attempt's ``token_base`` (partials past
        a lost checkpoint are ignored — determinism regenerates them)."""
        base = int(getattr(res, "token_base", 0) or 0)
        if base > len(freq.emitted) or not len(res.tokens):
            return
        merged = (np.concatenate([freq.emitted[:base], res.tokens])
                  if base else np.asarray(res.tokens, np.int32))
        if len(merged) > len(freq.emitted):
            freq.emitted = merged

    def _retire_failed(self, rep, freq, res):
        self._note_verdict(rep, freq.rid, ok=False)
        # exclude UNCONDITIONALLY: even when a hedge copy survives, a
        # later failover must not land back on the replica that already
        # failed this exact rid
        freq.excluded.add(rep.id)
        if freq.live:
            bump_counter("fleet.hedge_arm_failed")
            return  # the surviving hedge copy is the failover
        self._extend_emitted(freq, res)
        self._failover(freq, None,
                       f"replica {rep.id} failed it: {res.reason}")

    def _retire_timed_out(self, rep, freq, res):
        # the deadline is the CLIENT's budget: replaying elsewhere cannot
        # win back wall time that is already spent
        tokens = self._combine(freq, res)
        self._deliver(freq, "timed_out",
                      freq.emitted if tokens is None else tokens,
                      res.reason)

    def _retire_cancelled(self, rep, freq, res):
        if rep.state != "up":
            # a draining/dead replica handing the request back is not a
            # client cancel: requeue it (budget intact — no charge). A
            # surviving hedge copy IS the requeue — drop this arm.
            if freq.live:
                bump_counter("fleet.hedge_arm_dropped")
                return
            self._extend_emitted(freq, res)
            self._failover(freq, None,
                           f"replica {rep.id} drained", charge=False)
            return
        tokens = self._combine(freq, res)
        self._deliver(freq, "cancelled",
                      freq.emitted if tokens is None else tokens,
                      res.reason)

    def _retire_rejected(self, rep, freq, res):
        # the replica's admission control shed it; another replica may
        # have room (malformed requests reject everywhere and exhaust
        # the budget quickly)
        freq.excluded.add(rep.id)
        if freq.live:
            return
        self._failover(freq, None,
                       f"replica {rep.id} rejected it: {res.reason}")

    def _retire_unavailable(self, rep, freq, res):
        # the replica's own breaker refused it — evidence for the
        # router's breaker too, then reroute
        self._note_verdict(rep, freq.rid, ok=False)
        freq.excluded.add(rep.id)
        if freq.live:
            return
        self._failover(freq, None, f"replica {rep.id} unavailable")

    def _deliver(self, freq, status, tokens=None, reason=None):
        self._results[freq.rid] = RequestResult(
            freq.rid, status, tokens, reason)
        self._counts[status] = self._counts.get(status, 0) + 1
        if self._requests.pop(freq.rid, None) is not None:
            # release the tenant's outstanding quota hold (the single
            # terminal point every delivery path funnels through)
            left = (self._tenant_out.get(freq.tenant, 0)
                    - (int(freq.prompt.size) + freq.max_new_tokens))
            if left > 0:
                self._tenant_out[freq.tenant] = left
            else:
                self._tenant_out.pop(freq.tenant, None)
        if self._journal is not None:
            # terminal verdict journaled: GCs the live record and backs
            # the exactly-once resubmit cache (flushed at step/submit
            # boundaries — a crash in between replays the request, and
            # determinism re-derives the same verdict)
            self._journal.retire(freq.rid, status, tokens, reason)
        with contextlib.suppress(ValueError):
            self._parked.remove(freq.rid)
        if freq.rid in self._transfers:
            # delivered mid-hop (cancel, timeout, exhausted budget):
            # free the source's export pin and the ticket gauge
            self._release_export(self._transfers[freq.rid])
            self._clear_transfer(freq.rid)
        for rep_id in list(freq.live):
            rep = self._replicas.get(rep_id)
            if rep is None:
                continue
            rep.assigned.discard(freq.rid)
            if freq.rid in rep.probes:
                # this copy resolves with no verdict on the replica:
                # free the half-open probe slot it was riding
                rep.probes.discard(freq.rid)
                rep.breaker.release_probe()
            if rep.state == "up":
                try:
                    rep.frontend.cancel(freq.rid)
                except StaleLeaderError as e:
                    self._stand_down(str(e))
                except _TRANSPORT_ERRORS as e:
                    # a cancel that cannot reach the replica is replica
                    # death evidence like any other call — swallowing it
                    # would leave the corpse "up" to stall every future
                    # hedged delivery for the full rpc budget
                    self._kill_replica(rep,
                                       f"cancel transport error: {e!r}")
                except Exception:  # noqa: BLE001 — a failed cancel on a
                    # live replica only means the copy runs to completion
                    bump_counter("fleet.cancel_error")
        freq.live.clear()

    # --------------------------------------- prefill→decode handoff

    def _begin_handoff(self, rep, freq, res):
        """A prefill leg retired ``ok`` on ``rep``: export its KV hold
        as a transfer ticket, journal the hop (HANDOFF is admit-grade
        durable BEFORE any decode dispatch can ack), then drive the
        page transfer. Every failure here degrades to a colocated
        replay — the known first token keeps the replayed stream
        bit-identical."""
        tokens = self._combine(freq, res)
        if tokens is None or not len(tokens):
            freq.phase = None
            bump_counter("fleet.handoff_no_hold")
            self._failover(freq, None,
                           f"prefill on replica {rep.id} surfaced no "
                           "token; replaying colocated", charge=False)
            return
        try:
            ticket = rep.frontend.export_pages(freq.rid)
        except StaleLeaderError as e:
            self._stand_down(str(e))
            return
        except _TRANSPORT_ERRORS as e:
            # the source died between retiring the prefill and the
            # export: its pages died with it — plain failover
            self._kill_replica(rep, f"export transport error: {e!r}")
            if freq.rid in self._requests:
                freq.phase = None
                self._failover(
                    freq, None,
                    f"prefill source {rep.id} died before export")
            return
        if ticket is None:
            # the engine holds no pages for the rid (evicted, or the
            # prefill surfaced no first token): colocated replay
            freq.phase = None
            bump_counter("fleet.handoff_no_hold")
            self._failover(freq, None,
                           f"replica {rep.id} has no KV hold for the "
                           "handoff; replaying colocated", charge=False)
            return
        freq.phase = "decode"
        freq.emitted = np.asarray(tokens, np.int32)
        if self._journal is not None:
            # durable BEFORE the decode dispatch acks: a router crash
            # anywhere in the hop leaves a record take_over() re-drives
            # exactly once (handoff_done, or the retire, erases it)
            self._journal.handoff(freq.rid, source=rep.id,
                                  ticket=ticket["ticket"],
                                  first_token=int(freq.emitted[0]),
                                  prefill_len=int(freq.prompt.size))
            self._journal.flush()
        self._transfers[freq.rid] = {"ticket": ticket, "source": rep.id}
        bump_counter("fleet.transfer_started")
        if telemetry.enabled():
            _M_XFER_TICKET.set(1, rid=str(freq.rid),
                               ticket=str(ticket["ticket"])[:8],
                               src=str(rep.id))
            _M_XFER_INFLIGHT.set(len(self._transfers))
            telemetry.trace_event("fleet.handoff", trace=freq.trace,
                                  rid=freq.rid, source=rep.id,
                                  ticket=ticket["ticket"],
                                  pages=ticket["n_pages"])
        self._advance_handoff(freq)

    def _advance_handoff(self, freq):
        """Drive one live handoff forward: pick a decode destination,
        run the chunked CRC-framed transfer (``models/transfer.py``),
        dispatch the decode leg. No eligible destination parks the hop
        (``_pump_transfers`` retries it every step); destination
        failures charge the bounded transfer budget; source loss
        abandons the hop and re-prefills."""
        xfer = self._transfers.get(freq.rid)
        if xfer is None:
            return
        src = self._replicas.get(xfer["source"])
        if src is None or src.state != "up":
            self._abandon_transfer(
                freq, f"source replica {xfer['source']} died before "
                "the transfer")
            return
        ticket = xfer["ticket"]
        # phase=="decode" steers _candidates to the decode pool; the
        # SOURCE is excluded explicitly — its pages are already there,
        # and importing onto it would collide with its own export hold
        pool = [c for c in self._candidates(freq) if c[1] != src.id]
        if freq.rid not in self._requests:
            return  # a kill inside _candidates resolved the request
        if not pool:
            # no eligible destination AT ALL (breakers open, decode
            # pool dead): charge the transfer budget so the hop cannot
            # wait forever — on exhaustion degrade to a colocated
            # re-prefill (zero loss; the source's prefix cache makes
            # the replay cheap). TRANSIENT gaps (a cooldown expiring,
            # a scale-out landing) resume on an earlier retry.
            freq.transfers += 1
            if freq.transfers > self.max_transfer_retries:
                self._abandon_transfer(
                    freq, "no eligible decode destination")
            return
        dest = None
        for _, dest_id in pool:
            cand = self._replicas[dest_id]
            t0 = time.monotonic()
            try:
                transfer_pages(src.frontend, cand.frontend, ticket,
                               max_chunk_retries=self.max_transfer_retries)
                self._pump_s += time.monotonic() - t0
                dest = cand
                break
            except TransferNoCapacity:
                self._pump_s += time.monotonic() - t0
                # backpressure, not breakage: the pool is full NOW, the
                # same wait a colocated request queues through — try the
                # next destination, else retry the hop next step
                bump_counter("fleet.transfer_backpressure")
                continue
            except TransferSourceError as e:
                self._pump_s += time.monotonic() - t0
                self._abandon_transfer(freq, str(e))
                return
            except TransferDestError as e:
                self._pump_s += time.monotonic() - t0
                bump_counter("fleet.transfer_failed")
                # breaker evidence against the destination (a dead one
                # is ALSO killed by its next direct probe/collect), and
                # one charge against the bounded transfer budget
                self._note_verdict(cand, freq.rid, ok=False)
                freq.transfers += 1
                if freq.transfers > self.max_transfer_retries:
                    bump_counter("fleet.transfer_budget_exhausted")
                    self._deliver(freq, "failed", freq.emitted,
                                  f"transfer budget exhausted: {e}")
                return
        if dest is None:
            return  # every destination full; retried by _pump_transfers
        if not self._submit_to(freq, dest.id,
                               kv_import=ticket["ticket"]):
            # the destination died between landing the import and the
            # dispatch — the landed pages died with it; charge + retry
            bump_counter("fleet.transfer_failed")
            freq.transfers += 1
            if (freq.transfers > self.max_transfer_retries
                    and freq.rid in self._requests):
                bump_counter("fleet.transfer_budget_exhausted")
                self._deliver(freq, "failed", freq.emitted,
                              "transfer budget exhausted: decode "
                              "dispatch failed")
            return
        bump_counter("fleet.transfer_completed")
        if self._journal is not None:
            # the decode replica owns the request now: clear the hop so
            # a takeover does NOT re-drive it (PROGRESS/RETIRE records
            # cover recovery from here on)
            self._journal.handoff_done(freq.rid)
            self._journal.flush()
        self._release_export(xfer)
        self._clear_transfer(freq.rid)

    def _pump_transfers(self):
        """Retry handoffs that could not complete when they began (no
        eligible destination yet, a destination that failed) — called
        once per step so a parked hop resumes the moment the pool
        allows, and a hopeless one times out instead of hanging."""
        for rid in list(self._transfers):
            freq = self._requests.get(rid)
            if freq is None:
                # delivered out from under the hop (cancel/timeout
                # race): free the pin + gauge
                xfer = self._transfers.get(rid)
                if xfer is not None:
                    self._release_export(xfer)
                self._clear_transfer(rid)
                continue
            if freq.live:
                continue  # the decode leg is already out
            if freq.deadline.expired():
                self._deliver(freq, "timed_out", freq.emitted,
                              "expired awaiting the decode handoff")
                continue
            self._advance_handoff(freq)

    def _abandon_transfer(self, freq, reason):
        """The hop's pages are gone (source death, respawned source,
        lost/released ticket): drop it and replay the request from the
        known prefix — the prefill's first token is already in
        ``emitted``, so the replay resubmits ``prompt + [first]`` with
        ``token_base=1`` and the stream stays bit-identical."""
        xfer = self._transfers.get(freq.rid)
        if xfer is not None:
            # a LIVE source still pins the exported pages (e.g. the hop
            # was abandoned for want of a destination, not for source
            # death): free them BEFORE the replay — the re-prefill's
            # admission may need those very pages. No-op on a dead one.
            self._release_export(xfer)
        self._clear_transfer(freq.rid)
        bump_counter("fleet.transfer_abandoned")
        if self._journal is not None:
            # keep the first token durable past the record we clear
            self._journal.progress(freq.rid, freq.emitted)
            self._journal.handoff_done(freq.rid)
            self._journal.flush()
        freq.phase = None
        self._failover(freq, None, f"transfer abandoned: {reason}")

    def _release_export(self, xfer):
        """Best-effort release of the source's export pin (idempotent
        server-side). A failure is counted, not raised: a dead source's
        pages died with it, and a live one frees them at its next
        engine restart at the latest."""
        src = self._replicas.get(xfer["source"])
        if src is None or src.state != "up":
            return
        try:
            src.frontend.release_export(xfer["ticket"]["ticket"])
        except StaleLeaderError as e:
            self._stand_down(str(e))
        except Exception:  # noqa: BLE001 — best-effort cleanup; the
            # source's own death handling reclaims the pages
            bump_counter("fleet.release_export_failed")

    def _clear_transfer(self, rid):
        xfer = self._transfers.pop(rid, None)
        if xfer is None or not telemetry.enabled():
            return
        _M_XFER_TICKET.set(0, rid=str(rid),
                           ticket=str(xfer["ticket"]["ticket"])[:8],
                           src=str(xfer["source"]))
        _M_XFER_INFLIGHT.set(len(self._transfers))

    # --------------------------------------------------- liveness sweep

    def _sweep_liveness(self):
        if self._detector is None:
            return
        for rep_id in self._detector.dead_peers():
            rep = self._replicas.get(rep_id)
            if rep is not None and rep.state == "up":
                self._kill_replica(
                    rep, f"heartbeat lease ({self._lease:g}s) expired")

    # ------------------------------------------------------- takeover

    def _adopt_members(self):
        """Rebuild replica stubs from the membership registry the old
        leader published (remote replicas only — an in-process frontend
        cannot be re-addressed; tests hand those over via
        ``add_replica`` before takeover)."""
        if self._store is None:
            return
        key = f"{self._prefix}/members"
        if not self._store.check(key):
            return
        try:
            members = json.loads(self._store.get_now(key).decode())
        except (ValueError, KeyError, RuntimeError, ConnectionError,
                TimeoutError):
            bump_counter("fleet.members_unreadable")
            return
        from .remote import RemoteFrontend

        for rep_id, info in members.items():
            rep_id = int(rep_id)
            if info is None or rep_id in self._replicas:
                continue
            try:
                self.add_replica(RemoteFrontend(info["worker"],
                                                server=info["server"]),
                                 replica_id=rep_id)
            except Exception as e:  # noqa: BLE001 — a dead member must
                # not sink the takeover; its requests replay elsewhere
                bump_counter("fleet.member_adopt_failed")
                logger.warning("takeover: could not adopt replica %d "
                               "(%s)", rep_id, e)

    def take_over(self, timeout=None) -> dict:
        """Hot-standby promotion: block until the leader lease frees
        (clean release → ~0; crash → within one ttl), then replay the
        journal and resume serving exactly where the dead leader
        stopped:

        1. acquire the lease — the fencing token this takeover runs
           under is now the highest in the fleet;
        2. recover the journal (store index or ``journal_root``) into a
           fresh epoch file;
        3. rebuild replica stubs from the membership registry and
           **re-pin** every replica: the fence handshake makes the old
           leader's late writes bounce typed, and returns each
           replica's live request state;
        4. ADOPT running copies whose ``token_base`` sits inside the
           journaled prefix (their eventual results recombine exactly);
           cancel-and-replay copies that outran a lost checkpoint; and
           resubmit everything not live anywhere from its last
           checkpoint — all bit-identical to the uninterrupted run by
           the per-request key-stream contract.

        Returns a summary dict (requests/adopted/resubmitted/fence)."""
        if self._llease is None:
            raise ValueError("take_over() needs a leader_lease")
        if not self._llease.wait_acquire(timeout=timeout):
            raise TimeoutError(
                f"leader lease {self._llease.key!r} not acquired within "
                f"{timeout}s (holder still renewing)")
        fence = self._llease.fence
        self._standby = False
        self._deposed = False
        try:
            return self._promote(fence)
        except BaseException:
            # a FAILED promotion (journal unreadable, outranked by a
            # concurrent higher-fence takeover, ...) must not leave a
            # half-promoted leader that accepts submissions with no
            # replayed journal: restore standby state, drop the lease
            # hold, and let the caller retry take_over()
            self._standby = True
            if self._journal is not None:
                self._journal_root = self._journal.root
                with contextlib.suppress(Exception):
                    self._journal.close()
                self._journal = None
            with contextlib.suppress(Exception):
                self._llease.stand_down()
            raise

    def _promote(self, fence) -> dict:
        """The body of :meth:`take_over`, after the lease is held —
        split out so a failure anywhere rolls the router back to
        standby (see take_over's except)."""
        if self._journal is None:
            from .journal import RequestJournal

            self._journal = RequestJournal.recover(
                root=self._journal_root, epoch=fence, store=self._store,
                prefix=self._prefix)
        if self._store is not None:
            # the fleet now paces to THIS router's cadence (deferred
            # from __init__: a standby must not re-pace a live leader)
            with contextlib.suppress(Exception):
                self._store.set(f"{self._prefix}/hb_interval",
                                repr(self._hb_interval))
        self._adopt_members()
        self._publish_members()
        # re-pin: push the new fence + learn each replica's live state
        live_map: dict[int, list] = {}
        for rep in list(self._replicas.values()):
            if rep.state != "up":
                continue
            if hasattr(rep.frontend, "want_progress"):
                # replicas handed over pre-promotion (before the journal
                # existed) must start shipping the progress piggyback
                rep.frontend.want_progress = True
            t0 = time.monotonic()
            try:
                if getattr(rep.frontend, "is_remote", False):
                    info = rep.frontend.repin(fence)
                else:
                    info = rep.frontend.progress()
                self._pump_s += time.monotonic() - t0
            except StaleLeaderError:
                # a replica already serves a HIGHER fence: a concurrent
                # takeover outranks this one — abort the promotion (the
                # except in take_over rolls us back to standby)
                self._pump_s += time.monotonic() - t0
                raise
            except _TRANSPORT_ERRORS as e:
                self._pump_s += time.monotonic() - t0
                self._kill_replica(rep, f"repin transport error: {e!r}")
                continue
            for rid, (base, _toks) in info.items():
                live_map.setdefault(int(rid), []).append(
                    (rep, int(base)))
        state_n, adopted, resubmitted = self._restore_requests(live_map)
        bump_counter("fleet.takeover")
        telemetry.flight_recorder().record(
            "takeover", fence=fence, requests=state_n, adopted=adopted,
            resubmitted=resubmitted)
        if telemetry.enabled():
            for freq in self._requests.values():
                # hops across the LEADERSHIP boundary stitch too: the new
                # leader's fresh trace ids are announced against the rids
                telemetry.trace_event("fleet.takeover_adopt",
                                      trace=freq.trace, rid=freq.rid,
                                      fence=fence)
        logger.warning(
            "takeover complete (fence %d): %d journaled request(s) — "
            "%d running cop(ies) adopted, %d resubmitted", fence,
            state_n, adopted, resubmitted)
        return {"fence": fence, "requests": state_n,
                "adopted": adopted, "resubmitted": resubmitted}

    def _restore_requests(self, live_map) -> tuple:
        """Rebuild the request table from the journal's live state —
        the shared tail of a hot-standby promotion (``live_map`` from
        the re-pin handshake) and a restart-in-place recovery (empty
        ``live_map``: nothing is running anywhere, everything parks or
        resubmits). Seeds the rid counter past every journaled rid so a
        restarted router cannot alias one. Returns (journaled, adopted,
        resubmitted)."""
        state = self._journal.live_state()
        self._rids = itertools.count(
            max(self._journal.max_rid() + 1, next(self._rids)))
        adopted = resubmitted = 0
        for rid, rec in sorted(state.items()):
            remaining = None
            if rec["deadline_s"] is not None:
                remaining = (rec["deadline_s"]
                             - (time.time() - rec["admit_wall"]))  # wall-clock: x-process replay
            freq = _FleetRequest(rid, rec["prompt"], rec["max_new"],
                                 rec["prio"], Deadline(remaining),
                                 rec["hedge"],
                                 deadline_s=rec["deadline_s"],
                                 tenant=rec.get("tenant"))
            freq.emitted = np.asarray(rec["emitted"], np.int32)
            self._requests[rid] = freq
            # re-establish the tenant's quota hold for the recovered
            # request (released again at _deliver)
            self._tenant_out[freq.tenant] = (
                self._tenant_out.get(freq.tenant, 0)
                + int(freq.prompt.size) + freq.max_new_tokens)
            for rep, base in live_map.get(rid, ()):
                if base <= len(freq.emitted):
                    # the running copy's stream offset is inside our
                    # known prefix: keep it — its terminal result
                    # recombines exactly via token_base
                    freq.live.add(rep.id)
                    rep.assigned.add(rid)
                    adopted += 1
                else:
                    # the copy resumed past a checkpoint we lost:
                    # cancel it and replay from what we know (the
                    # discard row is swallowed in _collect)
                    try:
                        rep.frontend.cancel(rid)
                    except StaleLeaderError:
                        # a concurrent higher-fence takeover outranks
                        # this one mid-promotion: abort (take_over's
                        # except rolls us back to standby) — counting
                        # this as a mere cancel error would let the
                        # LOSER finish promoting and double-dispatch
                        raise
                    except _TRANSPORT_ERRORS as e:
                        self._kill_replica(
                            rep, f"cancel transport error: {e!r}")
                        continue
                    except Exception:  # noqa: BLE001 — replica-local
                        bump_counter("fleet.cancel_error")
                    rep.assigned.add(rid)
                    freq.live.add(rep.id)
                    freq.discard.add(rep.id)
                    freq.excluded.add(rep.id)
            ho = rec.get("handoff")
            if ho is not None:
                # the dead leader crashed MID-HANDOFF for this rid:
                # prefill done, decode dispatch not yet acked (the
                # window the HANDOFF record exists for)
                if freq.live - freq.discard:
                    # a live copy survived after all (the decode
                    # dispatch raced the crash): the hop completed —
                    # clear it so a later takeover won't re-drive it
                    self._journal.handoff_done(rid)
                elif self._redrive_handoff(freq, ho):
                    resubmitted += 1
                    continue
            if not (freq.live - freq.discard):
                if freq.discard:
                    continue  # replay resumes when the discard row lands
                resubmitted += 1
                if not self._dispatch(freq):
                    self._parked.append(rid)
        return len(state), adopted, resubmitted

    def _redrive_handoff(self, freq, ho) -> bool:
        """Resume one journaled mid-handoff hop after takeover. The
        source's ``export_pages`` is rid-idempotent — the dead leader
        never released the hold, so re-asking returns the SAME ticket
        and the hop re-drives exactly once. Returns False when the
        pages are gone (dead/respawned source): the caller re-prefills
        from the journaled prefix instead — first token included, so
        the stream is still bit-identical."""
        if (ho.get("first_token") is not None
                and not len(freq.emitted)):
            # the HANDOFF record outlives any progress checkpoint for
            # the first token: seed it so even the re-prefill path
            # resumes mid-stream instead of recomputing
            freq.emitted = np.asarray([ho["first_token"]], np.int32)
        src = self._replicas.get(ho.get("source"))
        ticket = None
        if src is not None and src.state == "up":
            try:
                ticket = src.frontend.export_pages(freq.rid)
            except StaleLeaderError:
                # a concurrent higher-fence takeover outranks this one
                # mid-promotion: abort (take_over rolls back to standby)
                raise
            except _TRANSPORT_ERRORS as e:
                self._kill_replica(
                    src, f"handoff re-export transport error: {e!r}")
        if ticket is None:
            # pages gone (source dead, respawned, or hold released):
            # clear the hop; the normal resubmit path re-prefills
            bump_counter("fleet.handoff_reprefill")
            if len(freq.emitted):
                self._journal.progress(freq.rid, freq.emitted)
            self._journal.handoff_done(freq.rid)
            freq.phase = None
            return False
        freq.phase = "decode"
        self._transfers[freq.rid] = {"ticket": ticket, "source": src.id}
        bump_counter("fleet.handoff_redriven")
        if telemetry.enabled():
            _M_XFER_TICKET.set(1, rid=str(freq.rid),
                               ticket=str(ticket["ticket"])[:8],
                               src=str(src.id))
            _M_XFER_INFLIGHT.set(len(self._transfers))
        self._advance_handoff(freq)
        return True

    # ------------------------------------------------------------ admin

    def attach_autoscaler(self, scaler):
        """Wire an ``models/autoscale.AutoScaler`` into the pump: every
        ``step()`` gives its (rate-limited) control loop a turn, so a
        fleet that is being pumped sizes itself without a separate
        driver thread. Returns the scaler for chaining."""
        self._autoscaler = scaler
        return scaler

    def warmup(self):
        """AOT-warm every replica's compiled serving shapes. A replica
        whose warmup fails at the TRANSPORT is classified dead (like any
        other call) rather than aborting the remaining replicas'
        warmups with the corpse left registered as up."""
        out = {}
        for rep in list(self._replicas.values()):
            if rep.state != "up":
                continue
            try:
                out[rep.id] = rep.frontend.warmup()
            except StaleLeaderError as e:
                self._stand_down(str(e))
                return out
            except _TRANSPORT_ERRORS as e:
                self._kill_replica(rep, f"warmup transport error: {e!r}")
        return out

    def shutdown(self, drain=True):
        """Drain (or hard-stop) every replica and deliver what resolves;
        anything still pending afterwards delivers ``unavailable``.

        A GRACEFUL shutdown also hands leadership over cleanly: the
        leader lease is RELEASED (deleted, not left to expire — a hot
        standby takes over in ~0 instead of waiting out a full ttl) and
        the router's own store keys (published heartbeat cadence,
        membership registry) are deleted so nothing stale outlives it."""
        for rep in list(self._replicas.values()):
            if rep.state == "up":
                with contextlib.suppress(Exception):
                    rep.frontend.shutdown(drain=drain)
                rep.state = "draining"
                self._collect(rep)
            self._deregister(rep)
        for freq in list(self._requests.values()):
            self._deliver(freq, "unavailable", freq.emitted,
                          "fleet shutdown")
        for rep in self._replicas.values():
            self._absorb_rpc_stats(rep)
            if telemetry.enabled():
                self._retire_replica_gauges(rep)
        self._replicas.clear()
        if self._detector is not None:
            with contextlib.suppress(Exception):
                self._detector.stop()
        if self._journal is not None:
            with contextlib.suppress(Exception):
                self._journal.close()
        if (self._store is not None and not self._standby
                and not self._deposed):
            # the LEADER's own keys must not linger: a stale hb_interval
            # would re-pace the next fleet epoch's replicas, and a stale
            # membership registry would have a future standby adopting
            # corpses. A standby/deposed router shutting down owns
            # neither key — deleting them here would clobber the live
            # leader's published state
            for key in (f"{self._prefix}/hb_interval",
                        f"{self._prefix}/members"):
                with contextlib.suppress(Exception):
                    self._store.delete_key(key)
        if self._llease is not None:
            # release, not expire: the standby's wait_acquire returns
            # the moment the record disappears
            with contextlib.suppress(Exception):
                self._llease.release()

    def _member_metric_snapshots(self) -> list:
        """Registry snapshots the replica PROCESSES published to the
        gang store on their heartbeat cadence (``replica_main``), for
        the current remote membership. In-process replicas share this
        process's registry and need no store hop."""
        snaps = []
        if self._store is None:
            return snaps
        for rep in list(self._replicas.values()):
            if rep.state == "dead":
                continue
            if not getattr(rep.frontend, "is_remote", False):
                continue
            key = f"{self._prefix}/metrics/{rep.id}"
            try:
                if self._store.check(key):
                    snaps.append(
                        json.loads(self._store.get_now(key).decode()))
            except (ValueError, KeyError, RuntimeError, ConnectionError,
                    TimeoutError):
                bump_counter("fleet.metrics_unreadable")
        return snaps

    _STATE_CODE = {"up": 1, "draining": 2, "dead": 0}
    _BREAKER_CODE = {CircuitBreaker.CLOSED: 0, CircuitBreaker.HALF_OPEN: 1,
                     CircuitBreaker.OPEN: 2}

    def _retire_replica_gauges(self, rep):
        """Final gauge export for a replica LEAVING the table (scale-in,
        shutdown): without it the last exported state ('up') freezes in
        every later snapshot and the roster lists the departed replica
        as alive forever."""
        rid = str(rep.id)
        _M_REP_STATE.set(0, replica=rid)
        _M_REP_ASSIGNED.set(0, replica=rid)

    def _export_replica_gauges(self):
        """Mirror the per-replica membership view (state, breaker,
        assignment, heartbeat age, incarnation) into labeled gauges so
        any snapshot of this registry carries the fleet roster — what
        ``obs fleet`` renders offline from a saved snapshot or a flight
        dump, when the live router is exactly the thing that died."""
        for rep in list(self._replicas.values()):
            rid = str(rep.id)
            _M_REP_STATE.set(self._STATE_CODE.get(rep.state, -1),
                             replica=rid)
            _M_REP_BREAKER.set(
                self._BREAKER_CODE.get(rep.breaker.state(), -1),
                replica=rid)
            _M_REP_ASSIGNED.set(len(rep.assigned), replica=rid)
            _M_REP_SERVED.set(rep.served, replica=rid)
            _M_REP_ROLE.set(1, replica=rid, role=rep.role)
            inc = (rep.h_cache or {}).get("_inc")
            if inc:
                _M_REP_INC.set(1, replica=rid, inc=str(inc)[:8])
            if self._store is not None and rep.state != "dead":
                with contextlib.suppress(Exception):
                    t = self._store.last_heartbeat(
                        rep.id, prefix=f"{self._prefix}/hb")
                    if t is not None:
                        _M_REP_HB_AGE.set(
                            max(time.time() - t, 0.0),  # wall-clock: x-process store beats
                            replica=rid)

    def fleet_metrics(self) -> dict:
        """ONE fleet-wide observability view: this process's telemetry
        registry merged with every replica process's store-published
        snapshot (``telemetry.merge_snapshots``). Answers the operator
        question in one call:

        * ``latency`` — fleet-wide TTFT / per-token / queue-wait
          p50/p95/p99 (merged histograms);
        * ``tokens_total`` and ``tokens_per_sec`` (rate over the window
          since the previous ``fleet_metrics()`` call);
        * ``replicas`` — per-replica state + router-side breaker state;
        * ``phases`` — fleet-wide step-time attribution (perfwatch
          ``serving.phase_s`` percentiles per scheduler phase);
        * ``slo`` — the declared TTFT/per-token objectives evaluated
          over the merged histograms (rolling goodput + multi-window
          burn rate + alarm);
        * ``tenants`` — per-tenant QoS view (TTFT/token/queue-wait
          percentiles, goodput at the TTFT objective, tokens served,
          shed/rejected/quota counts) from the tenant-labeled series;
        * ``brownout_stage`` — the brownout ladder stage from the
          merged ``serving.brownout_stage`` gauge (freshest snapshot
          wins — in an in-process fleet this is THE stage);
        * ``metrics`` — the full merged snapshot (counters incl. the
          whole resilience ledger, gauges, histograms) for export.
        """
        if telemetry.enabled():
            # refresh the roster gauges BEFORE snapshotting, so the
            # merged view (and anything that saves it) carries them
            self._export_replica_gauges()
        merged = telemetry.merge_snapshots(
            telemetry.registry().snapshot(),
            *self._member_metric_snapshots())
        tokens = merged["counters"].get("serving.tokens_total", 0)
        now = time.monotonic()
        rate = 0.0
        if self._fm_prev is not None:
            pt, pts = self._fm_prev
            if now > pts and tokens >= pt:
                rate = (tokens - pt) / (now - pts)
        self._fm_prev = (tokens, now)
        self._last_fleet = merged
        if self._slo_fleet is None:
            self._slo_fleet = perfwatch.SLOMonitor(
                source=lambda: self._last_fleet)
        return {
            "metrics": merged,
            "latency": latency_summaries(merged),
            # perfwatch: fleet-wide step-time attribution + SLO verdict
            # over the merged histograms
            "phases": (perfwatch.phase_summaries(merged)
                       if telemetry.enabled() else {}),
            "slo": (self._slo_fleet.status()
                    if telemetry.enabled() else {}),
            "tenants": (tenant_summaries(merged)
                        if telemetry.enabled() else {}),
            "brownout_stage": int(merged["gauges"].get(
                "serving.brownout_stage", 0)),
            "tokens_total": tokens,
            "tokens_per_sec": rate,
            "replicas": {r.id: {"state": r.state,
                                "breaker": r.breaker.state(),
                                "breaker_failures": r.breaker.failures,
                                "assigned": len(r.assigned),
                                "served": r.served,
                                "role": r.role}
                         for r in self._replicas.values()},
            "transfers_inflight": len(self._transfers),
            "pending": len(self._requests),
            "role": ("standby" if self._standby
                     else "deposed" if self._deposed else "leader"),
        }

    def health(self) -> dict:
        """Fleet-level snapshot: per-replica health + aggregate load."""
        reps = {}
        for rep in self._replicas.values():
            try:
                h = rep.frontend.health() if rep.state == "up" else {}
            except Exception:
                h = {}
            reps[rep.id] = {"state": rep.state,
                            "breaker": rep.breaker.state(),
                            "assigned": len(rep.assigned), **h}
        up = [r for r in self._replicas.values() if r.state == "up"]
        return {
            "replicas": reps,
            "up": len(up),
            "total": len(self._replicas),
            "pending": len(self._requests),
            "parked": len(self._parked),
            "ready": bool(up) and not self._standby and not self._deposed,
            "role": ("standby" if self._standby
                     else "deposed" if self._deposed else "leader"),
        }

    def stats(self) -> dict:
        """Router-side accounting. ``router_overhead_pct`` is the share
        of ACTIVE request-processing time spent in routing/bookkeeping
        outside the replica frontends — ``route_s / (route_s + pump_s)``,
        deliberately NOT route/wall: wall includes warmup and idle time,
        which would let an arbitrarily slow routing path pass the gate.
        The fleet acceptance gate records it as
        ``fleet_router_overhead_pct`` (< 5%).

        For a fleet of REMOTE replicas the same split also yields the
        transport gate: ``rpc_s`` is round-trip time inside
        ``RemoteFrontend`` calls, ``remote_exec_s`` the server-side
        execution those calls reported, and ``rpc_overhead_pct`` =
        (rpc_s − remote_exec_s) / active — wire+serialization time as a
        share of active processing (bench e3 gates it as
        ``fleet_rpc_overhead_pct`` < 10%)."""
        wall = time.monotonic() - self._t0
        active = self._route_s + self._pump_s
        rpc = dict(self._rpc_retired)
        for rep in self._replicas.values():
            self._fold_rpc_stats(rpc, rep.frontend)
        rpc_overhead = max(rpc["rpc_s"] - rpc["remote_exec_s"], 0.0)
        journal_s = (self._journal.write_s if self._journal is not None
                     else 0.0)
        return {
            "wall_s": wall,
            "route_s": self._route_s,
            "pump_s": self._pump_s,
            "router_overhead_pct": (100.0 * self._route_s / active
                                    if active > 0 else 0.0),
            # journal (WAL) cost as a share of active processing — the
            # bench e4 gate records it as router_journal_overhead_pct
            # (< 5%). journal_s is a SUBSET of route_s (appends happen
            # inside routing turns), split out for the gate.
            "journal_s": journal_s,
            "journal_overhead_pct": (100.0 * journal_s / active
                                     if active > 0 else 0.0),
            "rpc_s": rpc["rpc_s"],
            "remote_exec_s": rpc["remote_exec_s"],
            "rpc_calls": rpc["calls"],
            "rpc_overhead_s": rpc_overhead,
            "rpc_overhead_pct": (100.0 * rpc_overhead / active
                                 if active > 0 else 0.0),
            "replicas_up": sum(1 for r in self._replicas.values()
                               if r.state == "up"),
            "served_by_replica": {r.id: r.served
                                  for r in self._replicas.values()},
            # TTFT / per-token / queue-wait p50/p95/p99 from the registry
            # histograms: in-process fleets observe everything locally;
            # a fleet with REMOTE replicas reads the last fleet_metrics()
            # merge (the replica processes own the observations)
            "latency": latency_summaries(
                self._last_fleet
                if self._last_fleet is not None
                and any(getattr(r.frontend, "is_remote", False)
                        for r in self._replicas.values())
                else None),
            **{f"requests_{k}": v for k, v in sorted(self._counts.items())},
        }


def launch_fleet(entry, n_replicas, entry_args=(), max_restarts=3,
                 **launch_kwargs):
    """Run ``entry`` as ``n_replicas`` replica worker processes under the
    ``launch()`` supervisor with the serving failure domain:
    ``restart_policy="worker"`` (a crashed replica respawns ALONE within
    the restart budget while the survivors keep serving) and the
    supervisor's gang store exported for fleet heartbeats."""
    from ..distributed.launch import launch

    return launch(entry, entry_args=entry_args,
                  nproc_per_node=n_replicas, max_restarts=max_restarts,
                  restart_policy="worker", **launch_kwargs)
