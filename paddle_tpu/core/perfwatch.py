"""Performance observability over the PR 9 telemetry registry.

``core/telemetry.py`` answers "what is the fleet doing"; this layer
answers the PERFORMANCE questions the ROADMAP's open items need answered
in production before they can be attacked:

* **Step-time attribution** — where do a decode step's microseconds go?
  The serving engine observes every scheduler phase into ONE labeled
  histogram, ``serving.phase_s{phase=...}``:

  - ``prefill`` / ``chunked_prefill`` — admission dispatches (the call
    + the synchronous first-token fetch, so device time is included);
  - ``segment_dispatch`` — host time to build and issue one compiled
    decode segment (async: the device keeps running after it returns);
  - ``device_wait`` — the blocking ``device_get`` when a segment's
    outputs are consumed (device compute not hidden by the pipeline);
  - ``host_bookkeeping`` — token collection / retirement;
  - ``host_gap`` — the between-segment host gap ``stats()['host_gap_ms']``
    already tracks, now with a full distribution.

  :func:`phase_summaries` renders p50/p95/p99 + mean per phase from the
  live registry or any (fleet-merged) snapshot.

* **Memory watchdog** — :class:`MemoryWatchdog` polls
  ``paddle_tpu.device.memory_stats()`` (PJRT) into
  ``device.bytes_in_use`` / ``device.peak_bytes_in_use`` /
  ``device.bytes_limit`` gauges and fires a ``memory_hwm`` flight event
  (+ post-mortem dump, once per crossing with hysteresis) when usage
  crosses ``FLAGS_memory_hwm_pct`` of the limit. Backends without
  memory introspection (CPU) degrade GRACEFULLY: the gauges stay ABSENT
  — never zero/garbage — and
  ``perfwatch.memory_stats_unavailable`` counts the attempts. The
  engine adds the logical KV side (per-request bytes, slot occupancy,
  page fragmentation) in ``models/serving.py`` — the measurement side
  of the paged-KV item.

* **SLO monitor** — :class:`SLOMonitor` holds declared objectives
  (TTFT, per-token latency: a threshold in seconds + a target fraction)
  and computes rolling-window goodput and MULTI-WINDOW BURN RATE from
  the PR 9 serving histograms: each ``tick()`` snapshots the cumulative
  (total, good-within-threshold) pair per objective (good counts are
  interpolated from the histogram buckets at the threshold), and the
  burn rate over a window is ``error_rate / error_budget`` between the
  two snapshots bracketing it. The alarm flips when EVERY window burns
  above ``FLAGS_slo_burn_threshold`` (a short window alone is noise; a
  long window alone is too slow — the standard multi-window rule).
  ``ServingFrontend`` exposes the status in ``health()['slo']`` and —
  only behind ``FLAGS_slo_shedding`` — sheds admissions below
  ``FLAGS_slo_shed_below_priority`` while the alarm is up
  (``serving.slo_shed``); ``ServingRouter.fleet_metrics()['slo']``
  evaluates the same objectives over the fleet-merged histograms.

Everything here is default-on behind ``FLAGS_telemetry`` (the hot paths
observe only when ``telemetry.enabled()``); ``PERF.md`` holds what the
layer costs on the chip.
"""
from __future__ import annotations

import logging
import threading
import time

from . import telemetry
from .flags import define_flag, flag

logger = logging.getLogger("paddle_tpu.perfwatch")

__all__ = [
    "observe_phase", "phase_summaries", "PHASES",
    "kv_pool_summary",
    "MemoryWatchdog", "memory_watchdog",
    "SLOMonitor", "Objective", "default_objectives",
    "BrownoutController", "BROWNOUT_STAGES",
]

define_flag("FLAGS_memory_hwm_pct", 90.0,
            "Device-memory high watermark (% of bytes_limit) past which "
            "the memory watchdog records a memory_hwm flight event and "
            "dumps the flight recorder (once per crossing; re-arms when "
            "usage falls below ~80% of the watermark)")
define_flag("FLAGS_memory_poll_interval_s", 0.5,
            "Min seconds between device.memory_stats() polls on the "
            "serving path (maybe_poll rate limit)")
define_flag("FLAGS_slo_ttft_s", 1.0,
            "TTFT objective threshold (seconds) for the SLO monitor")
define_flag("FLAGS_slo_token_s", 0.25,
            "Per-token decode-latency objective threshold (seconds)")
define_flag("FLAGS_slo_target", 0.99,
            "SLO target fraction: this share of requests must land "
            "within the objective threshold (error budget = 1 - target)")
define_flag("FLAGS_slo_windows", "30,300",
            "Comma-separated burn-rate window lengths in seconds, "
            "shortest first (multi-window alarm: ALL must burn)")
define_flag("FLAGS_slo_burn_threshold", 2.0,
            "Burn-rate alarm threshold: error_rate/error_budget above "
            "this on EVERY window flips the alarm")
define_flag("FLAGS_slo_shedding", False,
            "When the SLO burn alarm is up, shed frontend admissions "
            "below FLAGS_slo_shed_below_priority (default OFF: the "
            "monitor observes; shedding is an explicit operator opt-in)")
define_flag("FLAGS_slo_shed_below_priority", 1,
            "Admissions with priority strictly below this are shed "
            "while the burn alarm is up (with FLAGS_slo_shedding on)")
define_flag("FLAGS_brownout", False,
            "Enable the staged brownout ladder (BrownoutController): "
            "under a sustained SLO burn alarm the frontend degrades in "
            "stages (cap max_new_tokens -> shed low priority -> shed "
            "over-share tenants -> protected class only) instead of the "
            "binary FLAGS_slo_shedding switch. Default OFF: degradation "
            "is an explicit operator opt-in. Requires FLAGS_telemetry: "
            "the burn-rate SENSOR reads the serving latency histograms, "
            "which are only observed with telemetry on (the ladder "
            "warns and stays at stage 0 otherwise).")
define_flag("FLAGS_brownout_token_cap", 0.25,
            "Brownout stage >= 1 multiplies each admission's requested "
            "max_new_tokens by this fraction (floor 1 token): shorter "
            "answers for everyone before anyone is turned away")
define_flag("FLAGS_brownout_hold_s", 30.0,
            "Min seconds between brownout stage transitions (both "
            "directions): the ladder escalates one stage per hold while "
            "the burn alarm stays up, and de-escalates one stage per "
            "hold once it clears — hysteresis against alarm flapping")
define_flag("FLAGS_brownout_protected_priority", 2,
            "Brownout stage 4 (protected_only) rejects every admission "
            "with priority strictly below this class")

# ------------------------------------------------------ phase attribution

PHASES = ("prefill", "chunked_prefill", "segment_dispatch", "device_wait",
          "host_bookkeeping", "host_gap")

# phase durations span ~10us (a pipelined dispatch) to seconds (a cold
# chunked prefill): finer-than-default low end
_PHASE_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3, 2.5e-3,
                  5e-3, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

_M_PHASE = telemetry.histogram(
    "serving.phase_s", "engine scheduler time by phase (prefill / "
    "chunked_prefill / segment_dispatch / device_wait / "
    "host_bookkeeping / host_gap) — see core/perfwatch.py for the "
    "device-vs-host semantics of each label", buckets=_PHASE_BUCKETS)


def observe_phase(phase, dur_s):
    """One phase observation. ``profiler.annotate(..., phase=)`` and
    ``profiler.record_span`` are its callers: a phase is the duration of
    the span of the same name, read once."""
    _M_PHASE.observe(dur_s, phase=phase)


def phase_summaries(snapshot=None) -> dict:
    """Per-phase p50/p95/p99 + count/mean (seconds) from the live
    registry, or from a (possibly fleet-merged) snapshot dict. Phases
    nobody observed are absent."""
    out = {}
    if snapshot is None:
        for key in _M_PHASE.series():
            phase = dict(key).get("phase")
            if phase is not None:
                out[phase] = _M_PHASE.summary(phase=phase)
        return out
    prefix = "serving.phase_s{"
    for name in (snapshot.get("histograms") or {}):
        if not name.startswith(prefix):
            continue
        labels = dict(p.split("=", 1)
                      for p in name[len(prefix):-1].split(","))
        phase = labels.get("phase")
        if phase is not None:
            out[phase] = telemetry.summary_from_snapshot(snapshot, name)
    return out


def kv_pool_summary(snapshot=None) -> dict:
    """KV page-pool pressure from the ``serving.kv_*`` / ``prefix_*``
    gauges and counters the engine exports — live registry or any
    (possibly fleet-merged) snapshot dict. The backend of ``obs kv``:
    pool occupancy, fragmentation, prefix-cache effectiveness, and
    per-slot granted-page counts (``serving.kv_slot_pages{slot=}``)."""
    if snapshot is None:
        snapshot = telemetry.registry().snapshot()
    gauges = snapshot.get("gauges") or {}
    counters = snapshot.get("counters") or {}
    slot_pages = {}
    prefix = "serving.kv_slot_pages{"
    for name, v in gauges.items():
        if name.startswith(prefix):
            labels = dict(p.split("=", 1)
                          for p in name[len(prefix):-1].split(","))
            if "slot" in labels:
                slot_pages[int(labels["slot"])] = int(v)
    return {
        "pages_total": gauges.get("serving.kv_pages_total"),
        "pages_free": gauges.get("serving.kv_pages_free"),
        "pages_pinned_export": gauges.get(
            "serving.kv_pages_pinned_export"),
        "bytes_in_use": gauges.get("serving.kv_bytes_in_use"),
        "slot_occupancy": gauges.get("serving.kv_slot_occupancy"),
        "fragmentation_pct": gauges.get("serving.kv_fragmentation_pct"),
        "prefix_hit_rate": gauges.get("serving.prefix_hit_rate"),
        "prefix_tokens_saved": counters.get(
            "serving.prefix_tokens_saved", 0),
        "pool_exhausted": counters.get("serving.kv_pool_exhausted", 0),
        "preempted": counters.get("serving.kv_preempted", 0),
        "slot_pages": slot_pages,
    }


# -------------------------------------------------------- memory watchdog

_M_MEM_USE = telemetry.gauge(
    "device.bytes_in_use", "PJRT allocator bytes in use (absent on "
    "backends without memory_stats)")
_M_MEM_PEAK = telemetry.gauge(
    "device.peak_bytes_in_use", "PJRT allocator peak bytes in use")
_M_MEM_LIMIT = telemetry.gauge(
    "device.bytes_limit", "PJRT allocator capacity")
_M_MEM_UNAVAIL = telemetry.counter(
    "perfwatch.memory_stats_unavailable", "memory_stats() polls that "
    "returned nothing (CPU backends) — the gauges stay absent")


class MemoryWatchdog:
    """Poll PJRT memory stats into gauges + a high-watermark flight
    event. One instance per process is enough (``memory_watchdog()``);
    ``maybe_poll()`` rate-limits itself so hot loops can call it
    unconditionally."""

    def __init__(self, device_id=0, hwm_pct=None, min_interval_s=None):
        self.device_id = int(device_id)
        self._hwm_pct = hwm_pct
        self._interval = min_interval_s
        self._lock = threading.Lock()
        self._last_poll = None
        self._hwm_fired = False
        self.available = None  # unknown until the first poll

    def poll(self):
        """One ``device.memory_stats()`` read. Returns the stats dict,
        or None when the backend exposes none — in which case the gauges
        are left ABSENT (a dashboard must read "no data", not "0 bytes
        on a 16GB chip")."""
        from .. import device as _device

        with self._lock:
            # maybe_poll() rate-limits on this stamp from other threads;
            # an unlocked write here could tear against its read-compare
            self._last_poll = time.monotonic()
        try:
            stats = _device.memory_stats(self.device_id) or {}
        except Exception:  # noqa: BLE001 — introspection must never
            # take down the serving path it watches
            stats = {}
        in_use = stats.get("bytes_in_use")
        if in_use is None:
            self.available = False
            _M_MEM_UNAVAIL.inc()
            return None
        self.available = True
        _M_MEM_USE.set(int(in_use))
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            _M_MEM_PEAK.set(int(peak))
        limit = stats.get("bytes_limit")
        if limit:
            _M_MEM_LIMIT.set(int(limit))
            self._check_hwm(int(in_use), int(limit))
        return stats

    def maybe_poll(self):
        """Rate-limited :meth:`poll` for per-step call sites."""
        interval = (self._interval if self._interval is not None
                    else float(flag("FLAGS_memory_poll_interval_s")))
        with self._lock:
            now = time.monotonic()
            if (self._last_poll is not None
                    and now - self._last_poll < interval):
                return None
            self._last_poll = now
        return self.poll()

    def _check_hwm(self, in_use, limit):
        hwm = (self._hwm_pct if self._hwm_pct is not None
               else float(flag("FLAGS_memory_hwm_pct"))) / 100.0
        pct = in_use / limit
        if pct >= hwm:
            if not self._hwm_fired:
                self._hwm_fired = True
                telemetry.flight_dump(
                    "memory_hwm", device=self.device_id,
                    bytes_in_use=in_use, bytes_limit=limit,
                    pct=round(100.0 * pct, 1))
        elif pct < hwm * 0.8:
            # hysteresis: don't re-dump on every oscillation around the
            # watermark, but a real second incident after recovery fires
            self._hwm_fired = False


_memwatch = MemoryWatchdog()


def memory_watchdog() -> MemoryWatchdog:
    return _memwatch


# ------------------------------------------------------------ SLO monitor

# SLO status exported as gauges so ANY registry snapshot (a replica's
# store-published one, a flight dump's embedded one) carries the burn
# verdict — the `obs slo` CLI renders these without a live monitor
_M_SLO_BURN = telemetry.gauge(
    "slo.burn", "burn rate (error_rate / error_budget) per objective "
    "and window, from the last SLOMonitor.status() evaluation")
_M_SLO_GOOD = telemetry.gauge(
    "slo.goodput", "rolling-window goodput per objective and window")
_M_SLO_ALARM = telemetry.gauge(
    "slo.alarm", "1 while the multi-window burn alarm is up, else 0")


class Objective:
    """One declared latency objective: ``target`` fraction of samples of
    histogram ``hist`` must land within ``threshold_s``."""

    __slots__ = ("name", "hist", "threshold_s", "target")

    def __init__(self, name, hist, threshold_s, target):
        self.name = str(name)
        self.hist = str(hist)
        self.threshold_s = float(threshold_s)
        self.target = float(target)
        if not 0.0 < self.target < 1.0:
            raise ValueError(
                f"SLO target must be in (0, 1), got {self.target}")


def default_objectives() -> list:
    """The declared serving objectives, from flags: TTFT and per-token
    decode latency over the PR 9 histograms."""
    target = float(flag("FLAGS_slo_target"))
    return [
        Objective("ttft", "serving.ttft_s",
                  flag("FLAGS_slo_ttft_s"), target),
        Objective("token_latency", "serving.token_latency_s",
                  flag("FLAGS_slo_token_s"), target),
    ]


def _count_within(row, threshold) -> float:
    """Samples <= threshold estimated from one histogram series row
    (``{count, bounds, buckets, sample}``) — cumulative finite buckets
    with linear interpolation inside the crossing bucket; the +inf
    bucket never counts as good. When the buckets are gone (a
    bounds-mismatched ``merge_snapshots`` invalidates them to None —
    mixed code versions in a rolling fleet), the merged RESERVOIR
    estimates the good fraction instead: reading a healthy fleet as
    0% goodput would flip a false burn alarm, the exact garbage-output
    case the merge hardening exists to prevent."""
    bounds = row.get("bounds") or ()
    buckets = row.get("buckets")
    if not bounds or not buckets:
        sample = row.get("sample") or ()
        if sample:
            frac = sum(1 for v in sample if v <= threshold) / len(sample)
            return float(row.get("count", 0)) * frac
        return 0.0
    acc = 0.0
    lo = 0.0
    for i, b in enumerate(bounds):
        c = buckets[i]
        if b <= threshold:
            acc += c
            lo = b
            continue
        if threshold > lo and b > lo:
            acc += c * (threshold - lo) / (b - lo)
        return acc
    return acc


class SLOMonitor:
    """Rolling-window goodput + multi-window burn rate over the serving
    latency histograms.

    ``tick(now)`` appends one cumulative ``(now, total, good)`` snapshot
    per objective (reading the process registry, or ``source()`` — a
    fleet-merged snapshot provider). ``status(now)`` computes, per
    objective and per window, the delta between the snapshot bracketing
    the window start and now:

    * ``goodput`` = good/total over the window (1.0 when idle — no
      traffic burns no budget);
    * ``burn`` = (1 - goodput) / (1 - target): 1.0 means errors arrive
      exactly at the budgeted rate; the alarm threshold (default 2.0)
      means the budget is burning at least twice too fast.

    The ALARM requires every window above threshold with at least
    ``min_count`` samples in the shortest one — a single slow request
    in an idle second must not shed traffic. Time is monotonic;
    ``now=`` overrides exist for deterministic drills."""

    def __init__(self, objectives=None, windows=None, burn_threshold=None,
                 min_count=8, source=None, shed_below=None):
        self.objectives = (list(objectives) if objectives is not None
                           else default_objectives())
        self._windows = windows
        self._burn_threshold = burn_threshold
        self.min_count = int(min_count)
        self._source = source
        self._shed_below = shed_below
        self._lock = threading.Lock()
        self._samples: dict[str, list] = {o.name: []
                                          for o in self.objectives}
        self._alarm = False
        self._status_cache = None   # (monotonic ts, status dict)

    def windows(self) -> tuple:
        if self._windows is not None:
            return tuple(self._windows)
        return tuple(sorted(float(w) for w in
                            str(flag("FLAGS_slo_windows")).split(",") if w))

    def burn_threshold(self) -> float:
        return (float(self._burn_threshold)
                if self._burn_threshold is not None
                else float(flag("FLAGS_slo_burn_threshold")))

    # ------------------------------------------------------------ ticking

    def _row(self, obj):
        """Cumulative (total, good) for one objective right now."""
        if self._source is not None:
            snap = self._source() or {}
            row = (snap.get("histograms") or {}).get(obj.hist)
        else:
            row = telemetry.histogram(obj.hist).snapshot_series().get(())
        if not row or not row.get("count"):
            return 0, 0.0
        return int(row["count"]), _count_within(row, obj.threshold_s)

    def tick(self, now=None):
        """Record one cumulative snapshot per objective and prune
        samples older than twice the longest window. Auto-clocked ticks
        (``now=None`` — health polls, pump turns) rate-limit themselves
        to ~10 per shortest window so a hot poll loop cannot grow the
        sample rings; an explicit ``now`` always records (drills)."""
        windows = self.windows()
        if now is None:
            now = time.monotonic()
            interval = max(min(windows) / 10.0, 0.05) if windows else 1.0
            with self._lock:
                rows = next(iter(self._samples.values()), None)
                if rows and now - rows[-1][0] < interval:
                    return
        else:
            now = float(now)
        horizon = now - 2.0 * (max(windows) if windows else 300.0)
        with self._lock:
            for obj in self.objectives:
                total, good = self._row(obj)
                rows = self._samples[obj.name]
                rows.append((now, total, good))
                while len(rows) > 1 and rows[0][0] < horizon:
                    rows.pop(0)

    # ------------------------------------------------------------- status

    def _window_delta(self, rows, now, window):
        """(d_total, d_good) between the newest snapshot at or before
        ``now - window`` (falling back to the oldest) and the latest."""
        if len(rows) < 2:
            return 0, 0.0
        cut = now - window
        base = rows[0]
        for r in rows:
            if r[0] <= cut:
                base = r
            else:
                break
        last = rows[-1]
        return max(last[1] - base[1], 0), max(last[2] - base[2], 0.0)

    def status(self, now=None) -> dict:
        """Tick, then evaluate every objective; updates the cached alarm
        :meth:`should_shed` reads. Plain ints/floats/bools — the dict
        rides ``health()`` across the RPC wire."""
        # auto-clocked calls (health polls, every pump turn) are served
        # from a short-lived cache on the tick cadence: the burn rate
        # only moves when a tick lands, and a hot pump loop must not pay
        # a full evaluation per step. Explicit ``now`` (drills) always
        # evaluates.
        if now is None:
            windows = self.windows()
            ttl = max(min(windows) / 10.0, 0.05) if windows else 1.0
            cached = self._status_cache
            t = time.monotonic()
            if cached is not None and t - cached[0] < ttl:
                return cached[1]
        # tick BEFORE resolving now: an auto-clocked call must keep the
        # tick's rate limiter engaged — appending (and then scanning) a
        # sample row per pump turn would grow without the traffic moving
        self.tick(now)
        now = time.monotonic() if now is None else float(now)
        threshold = self.burn_threshold()
        windows = self.windows()
        out = {"alarm": False, "burn_threshold": threshold,
               "windows_s": list(windows), "objectives": {}}
        any_alarm = False
        with self._lock:
            for obj in self.objectives:
                rows = self._samples[obj.name]
                burns = {}
                goodputs = {}
                counts = {}
                obj_alarm = len(windows) > 0
                for w in windows:
                    d_total, d_good = self._window_delta(rows, now, w)
                    key = f"{w:g}s"
                    counts[key] = d_total
                    if d_total <= 0:
                        goodputs[key] = 1.0
                        burns[key] = 0.0
                        obj_alarm = False
                        continue
                    gp = min(d_good / d_total, 1.0)
                    goodputs[key] = gp
                    burns[key] = (1.0 - gp) / max(1.0 - obj.target, 1e-9)
                    if burns[key] <= threshold:
                        obj_alarm = False
                # volume floor on the SHORTEST window: a single slow
                # request in an idle second is not an incident
                if (windows and counts.get(f"{min(windows):g}s", 0)
                        < self.min_count):
                    obj_alarm = False
                out["objectives"][obj.name] = {
                    "hist": obj.hist,
                    "threshold_s": obj.threshold_s,
                    "target": obj.target,
                    "goodput": goodputs,
                    "burn": burns,
                    "window_count": counts,
                    "alarm": obj_alarm,
                }
                any_alarm = any_alarm or obj_alarm
            self._alarm = any_alarm
        out["alarm"] = any_alarm
        if telemetry.enabled():
            for oname, o in out["objectives"].items():
                for key, burn in o["burn"].items():
                    _M_SLO_BURN.set(round(burn, 4), objective=oname,
                                    window=key)
                    _M_SLO_GOOD.set(round(o["goodput"][key], 4),
                                    objective=oname, window=key)
            _M_SLO_ALARM.set(1 if any_alarm else 0)
        self._status_cache = (time.monotonic(), out)
        return out

    def alarm(self) -> bool:
        """Cached verdict of the last :meth:`status` evaluation."""
        with self._lock:
            return self._alarm

    def should_shed(self, priority) -> bool:
        """True when burn-rate shedding is ON (``FLAGS_slo_shedding``),
        the alarm is up, and the admission's priority is below the
        protected class — the frontend's pre-queue check."""
        if not flag("FLAGS_slo_shedding") or not self.alarm():
            return False
        below = (self._shed_below if self._shed_below is not None
                 else int(flag("FLAGS_slo_shed_below_priority")))
        return int(priority) < below

    def burning_windows(self) -> dict:
        """``{objective: {window: burn}}`` for the windows currently
        above threshold in the LAST evaluated status — the trigger
        detail autoscaler/brownout flight events name, so a post-mortem
        says WHICH windows fired the actuator, not just that one did."""
        cached = self._status_cache
        if cached is None:
            return {}
        threshold = cached[1].get("burn_threshold", 0.0)
        out = {}
        for oname, o in cached[1].get("objectives", {}).items():
            hot = {w: round(b, 3) for w, b in o.get("burn", {}).items()
                   if b > threshold}
            if hot:
                out[oname] = hot
        return out


# --------------------------------------------------------- brownout ladder

# Degradation stages, in escalation order. Stage semantics are
# CUMULATIVE: stage 3 also applies stages 1-2's measures.
BROWNOUT_STAGES = ("normal", "token_cap", "shed_low_priority",
                   "shed_over_share", "protected_only")

_M_BROWNOUT_STAGE = telemetry.gauge(
    "serving.brownout_stage", "current brownout ladder stage (0=normal "
    "... 4=protected_only)")
_M_BROWNOUT_TRANS = telemetry.counter(
    "serving.brownout_transitions", "brownout stage transitions, by "
    "direction (up=escalate, down=recover)")
_M_BROWNOUT_SHED = telemetry.counter(
    "serving.brownout_shed", "admissions shed by the brownout ladder, "
    "by stage measure / tenant / priority")
_M_BROWNOUT_CAP = telemetry.counter(
    "serving.brownout_capped", "admissions whose max_new_tokens was "
    "shrunk by brownout stage >= 1 (token_cap)")


class BrownoutController:
    """Staged overload degradation driven by the SLO burn alarm.

    Instead of the binary ``FLAGS_slo_shedding`` switch, the ladder
    degrades (and recovers) one stage at a time, at most one transition
    per ``hold_s`` in either direction (hysteresis against alarm flap):

    == =================== ============================================
    0  normal              admit everything unchanged
    1  token_cap           shrink each admission's ``max_new_tokens``
                           to ``FLAGS_brownout_token_cap`` of the ask
    2  shed_low_priority   + shed priority < ``shed_below``
    3  shed_over_share     + shed tenants over their weight-fair share
                           of the outstanding work (``QoSPolicy``)
    4  protected_only      + reject everything below the protected
                           priority class
    == =================== ============================================

    Every transition bumps ``serving.brownout_transitions{direction=}``,
    moves the ``serving.brownout_stage`` gauge, and leaves a flight-
    recorder dump naming the burning windows — the ladder's history IS
    the incident's post-mortem. ``maybe_step()`` rate-limits itself on
    the monitor's tick cadence so pump loops call it unconditionally;
    an explicit ``now=`` (drills) always evaluates and uses the same
    virtual clock for the hold timers.

    The controller is inert (stage pinned 0, ``admit`` passes through)
    unless ``FLAGS_brownout`` is on or ``enabled=True`` is passed —
    same opt-in discipline as ``FLAGS_slo_shedding``.
    """

    def __init__(self, slo: SLOMonitor, qos=None, hold_s=None,
                 enabled=None, shed_below=None, protected=None,
                 token_cap=None, max_stage=None):
        self.slo = slo
        self.qos = qos
        self._hold_s = hold_s
        self._enabled = enabled
        self._shed_below = shed_below
        self._protected = protected
        self._token_cap = token_cap
        self.max_stage = int(max_stage if max_stage is not None
                             else len(BROWNOUT_STAGES) - 1)
        self.stage = 0
        self.transitions = 0
        self._last_change = None   # clock of the last transition
        self._last_eval = None
        self._warned_blind = False

    # ------------------------------------------------------------ config

    def enabled(self) -> bool:
        return (bool(flag("FLAGS_brownout")) if self._enabled is None
                else bool(self._enabled))

    def hold_s(self) -> float:
        return (float(flag("FLAGS_brownout_hold_s"))
                if self._hold_s is None else float(self._hold_s))

    def shed_below(self) -> int:
        return (int(flag("FLAGS_slo_shed_below_priority"))
                if self._shed_below is None else int(self._shed_below))

    def protected(self) -> int:
        return (int(flag("FLAGS_brownout_protected_priority"))
                if self._protected is None else int(self._protected))

    def token_cap(self) -> float:
        return (float(flag("FLAGS_brownout_token_cap"))
                if self._token_cap is None else float(self._token_cap))

    def stage_name(self) -> str:
        return BROWNOUT_STAGES[min(self.stage,
                                   len(BROWNOUT_STAGES) - 1)]

    # ---------------------------------------------------------- stepping

    def maybe_step(self, now=None) -> int:
        """Evaluate the alarm and move at most one stage. Auto-clocked
        calls (``now=None``) ride the SLO status cache, so a hot pump
        loop pays ~a dict read; explicit ``now`` always evaluates on
        that virtual clock (deterministic drills)."""
        if not self.enabled():
            return self.stage
        if not telemetry.enabled():
            # the ladder's SENSOR is the latency histograms, which are
            # only fed with telemetry on: an enabled ladder with a
            # blind sensor must say so instead of silently never acting
            if not self._warned_blind:
                self._warned_blind = True
                logger.warning(
                    "brownout ladder is enabled but FLAGS_telemetry=0: "
                    "the burn-rate sensor has no data — no degradation "
                    "will engage until telemetry is re-enabled")
            return self.stage
        status = self.slo.status(now=now)
        t = time.monotonic() if now is None else float(now)
        if self._last_eval is not None and t < self._last_eval:
            t = self._last_eval  # a virtual clock never runs backward
        self._last_eval = t
        alarm = bool(status.get("alarm"))
        if self._last_change is not None \
                and t - self._last_change < self.hold_s():
            return self.stage
        if alarm and self.stage < self.max_stage:
            self._transition(self.stage + 1, t, "up")
        elif not alarm and self.stage > 0:
            self._transition(self.stage - 1, t, "down")
        return self.stage

    def _transition(self, new_stage, t, direction):
        old, self.stage = self.stage, int(new_stage)
        self.transitions += 1
        self._last_change = t
        _M_BROWNOUT_STAGE.set(self.stage)
        _M_BROWNOUT_TRANS.inc(direction=direction)
        # every transition is a post-mortem moment: the dump's event
        # ring + metrics snapshot show what the ladder saw when it moved
        telemetry.flight_dump(
            "brownout", stage=self.stage, prev=old,
            stage_name=self.stage_name(), direction=direction,
            windows=self.slo.burning_windows())

    # ----------------------------------------------------------- verdict

    def admit(self, tenant, priority, max_new_tokens, over_share=None):
        """Admission verdict at the current stage: ``(action,
        max_new_tokens, reason)`` where action is ``"admit"`` or
        ``"shed"``. ``over_share`` is the caller's answer to "is this
        tenant over its fair share" (the frontend knows its usage map) —
        a bool, or a zero-arg callable evaluated only when stage >= 3
        actually needs it (the fair-share scan must not run per submit
        in the steady state); None means unknown — stage 3 then sheds
        nothing extra."""
        if self.stage <= 0 or not self.enabled():
            return "admit", max_new_tokens, None
        if self.stage >= 3 and callable(over_share):
            over_share = over_share()
        priority = int(priority)
        # local label form (models/qos.py tenant_label): core must not
        # import the models package (heavy, and layered above core)
        label = "-" if tenant is None else str(tenant)
        if self.stage >= 4 and priority < self.protected():
            _M_BROWNOUT_SHED.inc(measure="protected_only", tenant=label,
                                 priority=priority)
            return ("shed", max_new_tokens,
                    f"brownout stage {self.stage} (protected_only): "
                    f"priority {priority} below protected class "
                    f"{self.protected()}")
        if self.stage >= 3 and over_share:
            _M_BROWNOUT_SHED.inc(measure="over_share", tenant=label,
                                 priority=priority)
            return ("shed", max_new_tokens,
                    f"brownout stage {self.stage} (shed_over_share): "
                    f"tenant {label} is over its fair share")
        if self.stage >= 2 and priority < self.shed_below():
            _M_BROWNOUT_SHED.inc(measure="low_priority", tenant=label,
                                 priority=priority)
            return ("shed", max_new_tokens,
                    f"brownout stage {self.stage} (shed_low_priority): "
                    f"priority {priority} below {self.shed_below()}")
        capped = max(1, int(int(max_new_tokens) * self.token_cap()))
        if capped < int(max_new_tokens):
            _M_BROWNOUT_CAP.inc(tenant=label)
            return ("admit", capped,
                    f"brownout stage {self.stage}: max_new_tokens "
                    f"capped {max_new_tokens} -> {capped}")
        return "admit", max_new_tokens, None

    def status(self) -> dict:
        """Plain-JSON view for health payloads and the obs CLI."""
        return {"enabled": self.enabled(), "stage": self.stage,
                "stage_name": self.stage_name(),
                "transitions": self.transitions}
