"""Serving frontend: admission control, load shedding, circuit breaking,
graceful drain — the request-lifecycle layer over ContinuousBatchingEngine.

The engine (serving.py) is a pure scheduler: it decodes whatever sits in
its queue. Production traffic needs the layer above it — the part of a
vLLM-style serving stack that decides what is ALLOWED to reach the
scheduler and how the system degrades when it is saturated or broken:

* **Bounded admission queue** — ``submit()`` sheds load instead of
  buffering unboundedly: past ``max_queue`` entries or a
  ``max_queued_tokens`` backlog the request is ``"rejected"`` at the
  door. With priority classes, a higher-priority admission evicts the
  lowest-priority queued request (high-priority work sheds LAST).
* **Circuit breaker** — repeated engine-level failures (poison requests
  retired as ``"failed"``) trip a ``core.resilience.CircuitBreaker``;
  while it is open every submit fails fast as ``"unavailable"`` instead
  of feeding a broken engine, and a half-open probe request closes it
  again on success.
* **Graceful drain** — ``shutdown(drain=True)`` stops admitting,
  finishes the slots already decoding, and reports ``"cancelled"`` for
  everything still queued; ``drain=False`` cancels in-flight work too.
* **Health** — ``health()`` / ``ready()`` snapshots for watchdogs
  (``fleet.elastic.CommTaskManager`` can both scope ``step()`` under its
  timeout watch and poll ``ready`` as a registered probe).

The frontend is a synchronous pump: callers ``submit()`` whenever
requests arrive and drive progress with ``step()`` (one admit → decode →
retire turn) or ``results(wait=True)`` (pump until everything pending has
resolved). With the engine's overlapped scheduler (its default;
``pipeline=False`` builds the serial one) each pumped turn dispatches the
NEXT decode segment before consuming the previous one, so results arrive
one segment behind the device — admission control, poison bisection,
deadlines, and the circuit breaker are unchanged because the engine
drains its pipeline before any admission, bisection replay, or
mask-changing retirement. ``warmup()`` (delegated to the engine)
AOT-compiles every serving shape so the first request pays no compile
time. Request statuses:
``ok | timed_out | rejected | failed | cancelled | unavailable``.
"""
from __future__ import annotations

import bisect
import itertools
import time

import numpy as np

from ..core import perfwatch, telemetry
from ..core.resilience import CircuitBreaker, Deadline, bump_counter
from ..profiler import annotate
from .qos import FairClock, QoSPolicy, tenant_label
from .serving import TERMINAL_STATES as _ENGINE_TERMINAL

__all__ = ["ServingFrontend", "RequestResult", "TERMINAL_STATES",
           "latency_summaries"]

# Every terminal status a frontend result can carry: the engine's set
# plus the admission-level verdicts minted here. The fleet router's
# retirement switch is CI-gated against this set.
TERMINAL_STATES = frozenset(_ENGINE_TERMINAL | {"rejected", "unavailable"})

# admission-layer metrics (module-level handles — see serving.py note).
# serving.requests_total is shared with the engine: the engine stamps
# the terminal states of requests it admitted; the frontend stamps the
# verdicts the engine never saw (admission rejected/unavailable, queue
# expiry timed_out, queue cancels) — so the one labeled counter covers
# the whole status space.
_M_QWAIT = telemetry.histogram(
    "serving.queue_wait_s", "frontend admission-queue wait, submit -> "
    "engine admission")
_M_REQS = telemetry.counter("serving.requests_total")
_M_SLO_SHED = telemetry.counter(
    "serving.slo_shed", "admissions shed by the SLO burn-rate monitor "
    "(FLAGS_slo_shedding on, alarm up, priority below the protected "
    "class)")
# the admission-verdict counters also carry {tenant, priority}
# attribution series (label-less series = historical totals; labeled
# series answer WHOSE traffic was turned away during an incident)
_M_REJECTED = telemetry.counter("serving.rejected")
_M_SHED = telemetry.counter("serving.shed")
_M_QUOTA = telemetry.counter(
    "serving.quota_rejected", "admissions rejected because the tenant's "
    "outstanding token cost would exceed its QoS quota_tokens")

# the latency histograms every health/stats summary reads, keyed by the
# short name the payloads use
_LATENCY_HISTS = {"ttft_s": "serving.ttft_s",
                  "token_s": "serving.token_latency_s",
                  "queue_wait_s": "serving.queue_wait_s"}


def latency_summaries(snapshot=None) -> dict:
    """p50/p95/p99 + count/mean (seconds) for the serving latency
    histograms — from the process registry by default, or from a
    (possibly fleet-merged) ``MetricsRegistry.snapshot()`` dict. Shared
    by ``ServingFrontend.health()``, ``ServingRouter.stats()`` and
    ``ServingRouter.fleet_metrics()``."""
    out = {}
    for key, name in _LATENCY_HISTS.items():
        if snapshot is not None:
            out[key] = telemetry.summary_from_snapshot(snapshot, name)
        else:
            out[key] = telemetry.histogram(name).summary()
    return out


class RequestResult:
    """Terminal record for one submitted request. ``token_base`` is the
    sampling-stream offset the attempt was submitted with (the failover
    resume contract): ``tokens`` covers stream indices ``[token_base,
    token_base + len(tokens))``, so a fleet router recombines a resumed
    attempt as ``known_prefix[:token_base] + tokens`` instead of
    trusting that its emitted bookkeeping exactly matches the attempt."""

    __slots__ = ("rid", "status", "tokens", "reason", "token_base")

    def __init__(self, rid, status, tokens=None, reason=None,
                 token_base=0):
        self.rid = rid
        self.status = status
        self.tokens = (np.zeros((0,), np.int32) if tokens is None
                       else np.asarray(tokens, np.int32))
        self.reason = reason
        self.token_base = int(token_base)

    def __repr__(self):
        return (f"RequestResult(rid={self.rid}, status={self.status!r}, "
                f"tokens={len(self.tokens)})")


class _Pending:
    """A queued admission, ordered by (priority DESC, WFQ virtual
    finish tag ASC, arrival ASC). ``vft`` is the start-time-fair-queue
    tag (``qos.FairClock``): within one priority class tenants
    interleave by weighted share instead of raw arrival order — for a
    single tenant the tags are arrival-monotonic, so the historical
    FIFO-within-priority order is preserved bit-for-bit."""

    __slots__ = ("rid", "prompt", "max_new_tokens", "priority", "deadline",
                 "cost", "seq", "token_base", "trace", "tenant", "vft",
                 "t0m", "t0w", "hold_kv", "kv_import")

    def __init__(self, rid, prompt, max_new_tokens, priority, deadline,
                 seq, token_base=0, trace=None, tenant=None, vft=0.0,
                 hold_kv=False, kv_import=None):
        self.rid = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.priority = priority
        self.deadline = deadline
        # backlog cost: prompt tokens to prefill + tokens to decode
        self.cost = prompt.size + max_new_tokens
        self.seq = seq
        self.token_base = token_base
        self.trace = trace              # telemetry trace id
        self.tenant = tenant
        self.vft = float(vft)           # WFQ virtual finish tag
        self.t0m = time.monotonic()     # queue-wait anchor
        self.t0w = time.time()  # wall-clock: x-process trace epoch
        self.hold_kv = bool(hold_kv)    # disaggregated prefill leg
        self.kv_import = kv_import      # adopt this completed KV import

    def __lt__(self, other):
        return ((-self.priority, self.vft, self.seq)
                < (-other.priority, other.vft, other.seq))


class ServingFrontend:
    """submit()/results()/cancel() lifecycle over a
    ``ContinuousBatchingEngine`` (requests arrive over time, not as one
    list), with bounded admission, failure isolation surfaced as request
    statuses, a circuit breaker, and graceful drain.

    Usage::

        fe = ServingFrontend(engine, max_queue=32, max_queued_tokens=4096)
        rid = fe.submit(prompt, max_new_tokens=64, priority=1)
        for rid, res in fe.results(wait=True).items():
            print(rid, res.status, res.tokens)
        fe.shutdown(drain=True)
    """

    # the router treats local frontends and RemoteFrontend stubs
    # (models/remote.py) interchangeably; this flag picks the handling
    # that differs (who heartbeats, who pumps)
    is_remote = False

    def __init__(self, engine, max_queue=64, max_queued_tokens=None,
                 default_max_new_tokens=64, segment=16, breaker=None,
                 breaker_threshold=5, breaker_cooldown_s=30.0,
                 watchdog=None, watch_name="serving.step", slo=None,
                 qos=None, brownout=None, role="both"):
        self.engine = engine
        # disaggregation role this replica declares to the fleet router:
        # "prefill" (prompt leg only), "decode" (adopts transferred KV),
        # or "both" (colocated — the default, and the pre-disagg
        # behavior). Advisory: the ENGINE serves whatever arrives; the
        # router's candidate filter is what enforces pool membership,
        # so a role mismatch degrades to colocated serving, never loss.
        if role not in ("prefill", "decode", "both"):
            raise ValueError(f"role must be prefill|decode|both, "
                             f"got {role!r}")
        self.role = role
        # SLO monitor (perfwatch): declared TTFT / per-token objectives
        # evaluated over the process registry histograms. Always present
        # (status() is cheap and gated); shedding only ever engages
        # behind FLAGS_slo_shedding.
        self.slo = slo if slo is not None else perfwatch.SLOMonitor()
        # multi-tenant QoS: tenant weights feed the WFQ admission order,
        # quota_tokens bounds each tenant's outstanding cost. The
        # default policy has no quotas and uniform weights — tenant-less
        # traffic behaves exactly as before.
        self.qos = qos if qos is not None else QoSPolicy()
        self._fair = FairClock(self.qos)
        self._tenant_out: dict = {}   # tenant -> outstanding token cost
        self._req_cost: dict = {}     # rid -> (tenant, cost)
        # brownout ladder (perfwatch): staged degradation under a
        # sustained burn alarm. Inert unless FLAGS_brownout (or an
        # explicitly enabled controller) — same opt-in discipline as
        # FLAGS_slo_shedding.
        self.brownout = (brownout if brownout is not None
                         else perfwatch.BrownoutController(self.slo,
                                                           qos=self.qos))
        self.max_queue = int(max_queue)
        self.max_queued_tokens = max_queued_tokens
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.breaker = breaker or CircuitBreaker(
            "serving.engine", failure_threshold=breaker_threshold,
            cooldown_s=breaker_cooldown_s)
        self._watchdog = watchdog
        self._watch_name = watch_name
        self._queue: list[_Pending] = []   # sorted: high priority first
        self._inflight = {}                # rid -> engine Request
        self._probe_rids = set()           # half-open probes awaiting verdict
        self._results: dict[int, RequestResult] = {}
        self._rids = itertools.count()
        self._seq = itertools.count()
        self._draining = False
        self._closed = False
        self._segment = int(segment)
        engine.start(segment=segment)

    def warmup(self):
        """AOT-compile every engine shape at THIS frontend's segment
        length (see ``ContinuousBatchingEngine.warmup``) so the first
        submitted request hits only precompiled programs."""
        return self.engine.warmup(segment=self._segment)

    def fingerprint(self) -> tuple:
        """The engine identity a fleet router checks at registration:
        replicas serving the same weights with the same seed/sampling
        config produce bit-identical streams, which is the failover
        contract. Plain numbers so it crosses the RPC wire."""
        eng = self.engine
        return (eng._seed, eng.do_sample, eng.temperature, eng.top_k,
                eng.top_p, eng.eos_token_id)

    # ------------------------------------------------------------ admission

    def _finish(self, rid, status, tokens=None, reason=None,
                token_base=0):
        self._results[rid] = RequestResult(rid, status, tokens, reason,
                                           token_base=token_base)
        # quota accounting: a terminal verdict releases the tenant's
        # outstanding token cost (single release point — every path,
        # admission reject included, lands here)
        held = self._req_cost.pop(rid, None)
        if held is not None:
            tenant, cost = held
            left = self._tenant_out.get(tenant, 0) - cost
            if left > 0:
                self._tenant_out[tenant] = left
            else:
                self._tenant_out.pop(tenant, None)
        return rid

    def _reject(self, rid, reason, tenant=None, priority=0):
        bump_counter("serving.rejected")
        if telemetry.enabled():
            _M_REQS.inc(status="rejected")  # engine never saw it
            _M_REJECTED.inc(tenant=tenant_label(tenant),
                            priority=int(priority))
        self.engine.note_rejection()  # stats()['rejected'] sees shedding
        return self._finish(rid, "rejected", reason=reason)

    def _cancel_bookkeeping(self, rid, tokens=None, reason="",
                            token_base=0):
        self._inflight.pop(rid, None)
        bump_counter("serving.cancelled")
        self._finish(rid, "cancelled", tokens=tokens, reason=reason,
                     token_base=token_base)
        self._resolve_probe(rid, "cancelled")

    def queued_tokens(self) -> int:
        return sum(e.cost for e in self._queue)

    def submit(self, prompt, max_new_tokens=None, priority=0,
               deadline_s=None, rid=None, token_base=0,
               trace=None, tenant=None, hold_kv=False,
               kv_import=None) -> int:
        """Admit one request; returns its rid. Never raises for a bad or
        shed request — the verdict lands in ``results()`` as status
        ``rejected`` (admission control / malformed / tenant over
        quota), ``unavailable`` (circuit open), or a terminal decode
        status later. ``tenant`` selects the QoS lane: the tenant's WFQ
        weight orders it within its priority class, its ``quota_tokens``
        bounds the outstanding cost it may hold here, and its metrics
        series attribute the latency it sees.

        ``rid`` lets a caller that owns the request-id space (the fleet
        ``ServingRouter`` — sampling streams are keyed on the rid, so a
        failover replay must reuse it) name the request; a rid already
        pending here raises ``ValueError``. ``token_base`` is the
        engine's failover-resume contract (see
        ``ContinuousBatchingEngine.submit``). ``trace`` is the telemetry
        trace id the request's spans stitch under — a standalone
        frontend MINTS one here; a fleet router passes its own (minted
        at ``ServingRouter.submit``, riding the RPC envelope)."""
        if trace is None and telemetry.enabled():
            trace = telemetry.new_trace_id()
        if rid is None:
            rid = next(self._rids)
        else:
            if rid in self._inflight or any(e.rid == rid
                                            for e in self._queue):
                raise ValueError(f"rid {rid} is already pending on this "
                                 "frontend")
            if isinstance(rid, int) and rid >= 0:
                # keep auto rids strictly above explicit ones (no aliasing)
                self._rids = itertools.count(
                    max(rid + 1, next(self._rids)))
        if self._closed or self._draining:
            return self._reject(rid, "shutting down", tenant, priority)
        max_new = (self.default_max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if telemetry.enabled():
            # brownout ladder (FLAGS_brownout): staged degradation —
            # cap budgets, then shed low priority, then over-share
            # tenants, then everything below the protected class. Inert
            # at stage 0 / flag off. over_share is a thunk: the
            # fair-share scan only runs at stage >= 3, not per submit.
            act, max_new, why = self.brownout.admit(
                tenant, priority, max_new,
                over_share=lambda: self.qos.over_share(tenant,
                                                       self._tenant_out))
            if act == "shed":
                return self._reject(rid, why, tenant, priority)
            if self.slo.should_shed(priority):
                # legacy binary burn-rate shedding (FLAGS_slo_shedding):
                # while the SLO error budget burns past threshold,
                # low-priority admissions are turned away at the door so
                # the protected classes keep their latency
                _M_SLO_SHED.inc()
                _M_SLO_SHED.inc(tenant=tenant_label(tenant),
                                priority=int(priority))
                return self._reject(
                    rid, "slo burn-rate shed (error budget burning; "
                         f"priority {int(priority)} below protected "
                         "class)", tenant, priority)
        try:
            prompt = np.asarray(prompt).astype(np.int32).ravel()
            self.engine._validate(prompt, max_new)
        except (ValueError, TypeError) as e:
            # a request the engine could NEVER schedule is a poison pill
            # caught at the door — admission is where it must die, not
            # inside a co-batched dispatch
            return self._reject(rid, str(e), tenant, priority)
        # tenant token-budget quota: outstanding cost (queued + admitted,
        # prompt tokens + decode budget) may not exceed quota_tokens.
        # The frontend's submit never raises — the typed
        # TenantQuotaExceeded surface is the ROUTER's client API; here
        # the verdict is a "rejected" result with the same accounting.
        cost = int(prompt.size) + int(max_new)
        if not self.qos.check_quota(tenant,
                                    self._tenant_out.get(tenant, 0), cost):
            bump_counter("serving.quota_rejected")
            if telemetry.enabled():
                _M_QUOTA.inc(tenant=tenant_label(tenant))
            return self._reject(
                rid, f"tenant {tenant_label(tenant)} over quota "
                     f"({self._tenant_out.get(tenant, 0)} outstanding + "
                     f"{cost} > {self.qos.quota_tokens(tenant)} tokens)",
                tenant, priority)
        probe = False
        if self.breaker.state() != CircuitBreaker.CLOSED:
            # half-open admission goes through the breaker's own probe
            # accounting (allow() consumes one of half_open_max slots);
            # while open, allow() is False and we fail fast
            if not self.breaker.allow():
                bump_counter("serving.unavailable")
                if telemetry.enabled():
                    _M_REQS.inc(status="unavailable")
                return self._finish(
                    rid, "unavailable",
                    reason=f"circuit breaker {self.breaker.state()}")
            probe = True
        entry = _Pending(rid, prompt, max_new, int(priority),
                         (deadline_s if isinstance(deadline_s, Deadline)
                          else Deadline(deadline_s)), next(self._seq),
                         token_base=int(token_base), trace=trace,
                         tenant=tenant, hold_kv=hold_kv,
                         kv_import=kv_import)
        if telemetry.enabled():
            telemetry.trace_event("serving.submit", trace=trace, rid=rid,
                                  prompt_tokens=int(prompt.size),
                                  max_new=max_new, priority=int(priority))
        self._sweep_expired()  # dead entries must not shed live traffic
        # bounded admission: shed the lowest-priority queued request
        # (LAST in sorted order) while budgets are exceeded — but only
        # after proving the newcomer CAN fit once every out-ranked entry
        # is gone; an infeasible request must not empty the queue first
        if self._over_budget(entry) and not self._feasible(entry):
            if probe:
                self.breaker.release_probe()
            return self._reject(
                rid, f"admission queue full "
                     f"(depth {len(self._queue)}/{self.max_queue})",
                tenant, priority)
        while self._over_budget(entry):
            # _feasible guarantees the tail outranks nothing: every
            # remaining over-budget token/slot is held by a lower-priority
            # entry, so the victim is always evictable
            victim = self._queue.pop()
            bump_counter("serving.shed")
            if telemetry.enabled():
                _M_SHED.inc(tenant=tenant_label(victim.tenant),
                            priority=int(victim.priority))
            self._reject(victim.rid, "shed by higher-priority admission",
                         victim.tenant, victim.priority)
            self._resolve_probe(victim.rid, "rejected")
        # the WFQ tag is charged to the tenant's lane only once the
        # entry is ACCEPTED: a queue-full rejection must not push the
        # tenant's virtual start time into the future, or a burst of
        # rejections would deprioritize its post-overload traffic
        entry.vft = self._fair.tag(entry.priority, tenant, entry.cost)
        # quota accounting: the entry now holds its cost until terminal
        self._req_cost[rid] = (tenant, entry.cost)
        self._tenant_out[tenant] = (self._tenant_out.get(tenant, 0)
                                    + entry.cost)
        bisect.insort(self._queue, entry)
        if probe:
            self._probe_rids.add(rid)
        return rid

    def _over_budget(self, entry) -> bool:
        if len(self._queue) + 1 > self.max_queue:
            return True
        if self.max_queued_tokens is not None:
            return self.queued_tokens() + entry.cost > self.max_queued_tokens
        return False

    def _feasible(self, entry) -> bool:
        """Could ``entry`` fit the budgets after evicting every queued
        request it outranks? (Entries of equal/higher priority are never
        evicted on its behalf.)"""
        kept = [e for e in self._queue if e.priority >= entry.priority]
        if len(kept) + 1 > self.max_queue:
            return False
        if self.max_queued_tokens is not None:
            return (sum(e.cost for e in kept) + entry.cost
                    <= self.max_queued_tokens)
        return True

    # ------------------------------------------------------------- pumping

    def _watched(self, fn):
        """Run ``fn`` under the watchdog's watch scope (when given) so a
        wedged engine dispatch trips the ``CommTaskManager`` timeout
        dump."""
        if self._watchdog is not None:
            from ..distributed.fleet.elastic import watch

            with watch(self._watchdog, self._watch_name):
                return fn()
        return fn()

    def step(self):
        """One scheduler turn: move admissible queued requests into the
        engine's free slots, run one decode segment, record outcomes —
        watchdog-scoped."""
        return self._watched(self._step)

    def _sweep_expired(self):
        """Retire queue entries whose deadline ran out, independent of
        free slots: while the engine is saturated they would otherwise
        keep pinning the queue/backlog budgets and shed live traffic for
        dead work. Runs on every step AND every admission attempt."""
        live = []
        for entry in self._queue:
            if entry.deadline.expired():
                if telemetry.enabled():
                    _M_REQS.inc(status="timed_out")  # engine never saw it
                self._finish(entry.rid, "timed_out",
                             reason="expired while queued",
                             token_base=entry.token_base)
                self._resolve_probe(entry.rid, "timed_out")
            else:
                live.append(entry)
        self._queue = live

    def _step(self):
        # one span tree per turn: frontend_step > {frontend_tick,
        # frontend_admit, serving.turn (the engine's), frontend_record}
        with annotate("serving.frontend_step"):
            with annotate("serving.frontend_tick"):
                if telemetry.enabled():
                    # keep the burn-rate windows current even when nobody
                    # polls health(); rate-limited inside the monitor —
                    # and let the brownout ladder step with the alarm
                    # (inert unless enabled)
                    self.slo.status()
                    self.brownout.maybe_step()
                self._sweep_expired()
            with annotate("serving.frontend_admit"):
                self._admit_from_queue()
            if self.engine.has_work():
                finished = self.engine.step()
                with annotate("serving.frontend_record"):
                    self._record(finished)

    def _admit_from_queue(self):
        room = self.engine.free_slots() - len(self.engine.queued_requests())
        if getattr(self.engine, "admission_blocked", False):
            # the engine's KV page pool deferred its queue head last
            # step: hold admissions HERE, in the priority/WFQ queue,
            # instead of spilling them into the engine's FIFO where
            # priority ordering no longer applies
            room = 0
        while room > 0 and self._queue:
            entry = self._queue.pop(0)
            # WFQ: dispatching advances the class's virtual clock so
            # late-arriving tenants start at the present
            self._fair.advance(entry.priority, entry.vft)
            req = self.engine.submit(entry.prompt, entry.max_new_tokens,
                                     deadline_s=entry.deadline,
                                     rid=entry.rid,
                                     token_base=entry.token_base,
                                     trace=entry.trace,
                                     tenant=entry.tenant,
                                     hold_kv=entry.hold_kv,
                                     kv_import=entry.kv_import)
            # TTFT anchors at frontend SUBMIT time, not engine admission
            # — queue wait is part of the latency a client sees
            req.t_submit = entry.t0m
            if telemetry.enabled():
                wait = time.monotonic() - entry.t0m
                _M_QWAIT.observe(wait)
                if entry.tenant is not None:
                    # per-tenant series: the WFQ fairness bound ("a hot
                    # tenant cannot blow a quiet tenant's queue wait")
                    # is asserted on exactly this attribution
                    _M_QWAIT.observe(wait, tenant=str(entry.tenant))
                telemetry.tracer().add_span(
                    "serving.queue_wait", entry.t0w, wait, t0=entry.t0m,
                    trace=entry.trace, rid=entry.rid)
            self._inflight[entry.rid] = req
            room -= 1

    def _record(self, finished):
        for req in finished:
            self._inflight.pop(req.rid, None)
            self._finish(req.rid, req.status, tokens=req.output(),
                         reason=(str(req.error) if req.error is not None
                                 else None), token_base=req.token_base)
            if req.status == "failed":
                # while recovering, only a PROBE's failure re-trips; a
                # stale failure from pre-trip work is not probe evidence
                if (self.breaker.state() != CircuitBreaker.HALF_OPEN
                        or req.rid in self._probe_rids):
                    self.breaker.record_failure()
            elif req.status == "ok":
                # while recovering, only an admitted PROBE's success is
                # evidence the engine healed — a stale ok from pre-trip
                # work must not close the breaker on its behalf
                if (self.breaker.state() == CircuitBreaker.CLOSED
                        or req.rid in self._probe_rids):
                    self.breaker.record_success()
            self._resolve_probe(req.rid, req.status)

    def _resolve_probe(self, rid, status):
        """A half-open probe that resolved WITHOUT a verdict on the engine
        (cancelled / its own deadline) frees its probe slot; ok/failed
        verdicts already closed or re-opened the breaker."""
        if rid in self._probe_rids:
            self._probe_rids.discard(rid)
            if status not in ("ok", "failed"):
                self.breaker.release_probe()

    def pending(self) -> int:
        """Requests submitted but without a terminal result yet (engine-
        queued requests are already tracked in ``_inflight``)."""
        return len(self._queue) + len(self._inflight)

    def progress(self) -> dict:
        """Live (non-terminal) request state as ``{rid: (token_base,
        emitted_tokens)}`` — queued entries report an empty emission.
        This is the stream a fleet router journals as PROGRESS
        checkpoints (every K tokens) and the state a hot-standby router
        adopts at takeover: a copy whose ``token_base`` is within the
        journaled prefix keeps running; anything else is cancelled and
        resubmitted from the last checkpoint, bit-identically."""
        out = {}
        for entry in self._queue:
            out[entry.rid] = (int(entry.token_base),
                              np.zeros((0,), np.int32))
        for rid, req in self._inflight.items():
            out[rid] = (int(req.token_base),
                        np.asarray(req.output(), np.int32))
        return out

    def results(self, wait=False, timeout=None) -> dict:
        """Pop terminal results as ``{rid: RequestResult}``. With
        ``wait=True`` the frontend pumps ``step()`` until every pending
        request resolves (bounded by ``timeout`` seconds when given —
        the same per-call budget a ``RemoteFrontend`` stub honors)."""
        if wait:
            deadline = Deadline(timeout)
            while ((self.pending() or self.engine.has_work())
                   and not deadline.expired()):
                self.step()
        out, self._results = self._results, {}
        return out

    def cancel(self, rid) -> bool:
        """Cancel a queued or in-flight request; its partial tokens (if
        any) land in results with status ``"cancelled"``. Returns False
        when the rid is unknown or already terminal."""
        for entry in self._queue:
            if entry.rid == rid:
                self._queue.remove(entry)
                if telemetry.enabled():
                    _M_REQS.inc(status="cancelled")  # engine never saw it
                self._cancel_bookkeeping(rid, reason="cancelled in queue",
                                         token_base=entry.token_base)
                return True
        req = self.engine.abort(rid, "cancelled")
        if req is not None:
            self._cancel_bookkeeping(rid, tokens=req.output(),
                                     reason="cancelled in flight",
                                     token_base=req.token_base)
            return True
        return False

    # --------------------------------------- KV page transfer passthrough
    # The router drives the prefill→decode handoff against frontends
    # (local here, RemoteFrontend stubs in a fleet); these delegate to
    # the engine's primitive surface so both sides expose one API.

    def export_pages(self, rid):
        """Mint (or re-serve) the KV transfer ticket for ``rid``'s held
        prefill pages (see ``ContinuousBatchingEngine.export_pages``)."""
        return self.engine.export_pages(rid)

    def transfer_chunk(self, ticket, idx):
        """Serve one CRC-framed chunk of a live export."""
        return self.engine.transfer_chunk(ticket, idx)

    def import_kv_chunk(self, meta, idx, payk, payv, crc):
        """Land one chunk of an inbound transfer (idempotent by
        ticket + index)."""
        return self.engine.import_kv_chunk(meta, idx, payk, payv, crc)

    def release_export(self, ticket) -> bool:
        """Drop a finished/abandoned export's page pin (idempotent)."""
        return self.engine.release_export(ticket)

    def drop_import(self, ticket) -> bool:
        """Abandon a partial inbound transfer, freeing its local page
        grants (idempotent)."""
        return self.engine.drop_import(ticket)

    # ------------------------------------------------------------ shutdown

    def shutdown(self, drain=True):
        """Stop admitting. ``drain=True`` finishes the requests already
        holding slots (their results arrive normally) and reports
        ``"cancelled"`` for everything still queued; ``drain=False`` also
        cancels the in-flight slots, keeping their partial tokens."""
        if self._closed:
            return
        self._draining = True
        for entry in self._queue:
            if telemetry.enabled():
                _M_REQS.inc(status="cancelled")  # engine never saw it
            self._cancel_bookkeeping(entry.rid,
                                     reason="shutdown before admission",
                                     token_base=entry.token_base)
        self._queue.clear()
        for req in self.engine.queued_requests():
            self.engine.abort(req.rid, "cancelled")
            self._cancel_bookkeeping(req.rid, tokens=req.output(),
                                     reason="shutdown before a slot was "
                                            "assigned",
                                     token_base=req.token_base)
        if drain:
            # the drain pump stays under the watchdog scope: a dispatch
            # that wedges DURING shutdown still trips the timeout dump
            while self.engine.has_work():
                self._watched(lambda: self._record(self.engine.step()))
        else:
            for req in list(self.engine.active_requests()):
                self.engine.abort(req.rid, "cancelled")
                self._cancel_bookkeeping(req.rid, tokens=req.output(),
                                         reason="shutdown cancelled "
                                                "in-flight",
                                         token_base=req.token_base)
            # cancelling in-flight slots can strand a dispatched-but-
            # unconsumed pipeline segment; drain it so the engine ends
            # the session clean (its emissions are discarded — every
            # request is already terminal)
            while self.engine.has_work():
                self._watched(lambda: self._record(self.engine.step()))
        self._closed = True

    # -------------------------------------------------------------- health

    def ready(self) -> bool:
        """Admitting traffic right now? (False while draining, stopped,
        or with the breaker open — the state an elastic watchdog polls
        before routing work here.)"""
        return (not self._closed and not self._draining
                and self.breaker.state() != CircuitBreaker.OPEN)

    def health(self) -> dict:
        """Snapshot for watchdogs and load-balancers — ONE machine-readable
        payload (plain ints/floats/strings only) with everything a router
        needs to score and gate this replica:

        * overall ``state`` (``ok | degraded | draining | unavailable |
          stopped``) and ``ready``;
        * breaker detail: ``breaker`` state plus ``breaker_failures``
          (consecutive failures while closed — a replica drifting toward
          its trip point scores worse before it trips);
        * load: ``queue_depth`` / ``queued_tokens`` backlog,
          ``queue_by_priority`` per request class (``{priority: [depth,
          queued_tokens]}``), and ``inflight`` (admitted to the engine,
          not yet terminal);
        * KV-slot occupancy: ``active_slots`` / ``free_slots`` /
          ``kv_slots`` (total) / ``kv_occupancy`` (active/total); page
          POOL pressure: ``kv_pages_free`` / ``kv_pages_total`` /
          ``kv_fragmentation_pct`` / ``prefix_hit_rate`` (the dynamic
          allocator's admission headroom — a router can prefer replicas
          with page headroom, not just free slots);
        * ``latency``: recent-window percentile summaries (p50/p95/p99 +
          count/mean, seconds) for TTFT, per-token decode latency, and
          admission-queue wait — sourced from the telemetry registry
          histograms (``serving.ttft_s`` / ``serving.token_latency_s`` /
          ``serving.queue_wait_s``), which are PROCESS-scoped: in a
          one-replica-per-process fleet this is the replica's view.
        """
        breaker_state = self.breaker.state()
        if self._closed:
            state = "stopped"
        elif self._draining:
            state = "draining"
        elif breaker_state == CircuitBreaker.OPEN:
            state = "unavailable"
        elif breaker_state == CircuitBreaker.HALF_OPEN:
            state = "degraded"
        else:
            state = "ok"
        by_prio: dict[int, list] = {}
        by_tenant: dict[str, list] = {}
        for e in self._queue:
            row = by_prio.setdefault(int(e.priority), [0, 0])
            row[0] += 1
            row[1] += e.cost
            trow = by_tenant.setdefault(tenant_label(e.tenant), [0, 0])
            trow[0] += 1
            trow[1] += e.cost
        active = len(self.engine.active_requests())
        total = int(self.engine.max_slots)
        kv = (self.engine.kv_stats()
              if hasattr(self.engine, "kv_stats") else {})
        return {
            "state": state,
            "ready": self.ready(),
            "role": self.role,
            "breaker": breaker_state,
            "breaker_failures": self.breaker.failures,
            "draining": self._draining,
            "queue_depth": len(self._queue),
            "queued_tokens": self.queued_tokens(),
            "queue_by_priority": by_prio,
            "queue_by_tenant": by_tenant,
            # per-tenant OUTSTANDING token cost (queued + in-flight):
            # the quantity quota_tokens bounds
            "tenant_outstanding": {tenant_label(t): int(c)
                                   for t, c in self._tenant_out.items()},
            "inflight": len(self._inflight),
            "active_slots": active,
            "free_slots": self.engine.free_slots(),
            "kv_slots": total,
            "kv_occupancy": (active / total) if total else 0.0,
            "kv_pages_free": int(kv.get("pages_free", 0)),
            "kv_pages_total": int(kv.get("pages_total", 0)),
            "kv_fragmentation_pct": float(
                kv.get("fragmentation_pct", 0.0)),
            "prefix_hit_rate": float(kv.get("prefix_hit_rate", 0.0)),
            "kv_admission_blocked": bool(
                getattr(self.engine, "admission_blocked", False)),
            "latency": latency_summaries(),
            # perfwatch SLO verdict: objectives, rolling goodput,
            # multi-window burn rate, the alarm the shedding flag acts on
            "slo": (self.slo.status() if telemetry.enabled() else {}),
            # brownout ladder stage (0 unless FLAGS_brownout engaged it)
            "brownout": self.brownout.status(),
        }
