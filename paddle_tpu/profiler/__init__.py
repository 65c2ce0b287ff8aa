"""paddle_tpu.profiler — tracing and profiling.

Analog of /root/reference/python/paddle/profiler/ (Profiler:358 with
scheduler states, export_chrome_tracing, RecordEvent spans; C++ CUPTI
tracers in paddle/fluid/platform/profiler/). TPU-natively device timelines
come from the XLA/XPlane profiler (``jax.profiler``) — the CUPTI
equivalent — and host-side phases from RecordEvent spans recorded here and
via ``jax.profiler.TraceAnnotation``. ``programs.py`` keeps, for every
program the engine and the train step compile, which ``jax.named_scope``
and which pass each HLO instruction came from (``note_program`` /
``program_ops`` / ``attribute_device_time``): what turns a device trace's
``fusion.2244`` into "the MLP's backward".
"""
from __future__ import annotations

import glob
import json
import os
import tempfile
import time

import jax.profiler
from jax.profiler import TraceAnnotation

from ..core import perfwatch, telemetry
from .programs import (ProgramTable, attribute_device_time,  # noqa: F401
                       device_op_seconds, note_program, program_ops,
                       program_table)

__all__ = [
    "Profiler", "ProfilerResult", "RecordEvent", "ProfilerTarget",
    "ProfilerState", "annotate", "record_span", "make_scheduler",
    "export_chrome_tracing", "load_profiler_result",
    "note_program", "program_ops", "attribute_device_time", "program_table",
    "ProgramTable", "device_op_seconds",
]

# a Profiler session is running: spans record into the sink for its
# duration even with FLAGS_telemetry=0
_active = False


def _recording() -> bool:
    return _active or telemetry.enabled()


class annotate:
    """THE way a region of host code gets measured: a
    ``jax.profiler.TraceAnnotation`` of the same name (so the region is on
    the device trace's clock in a TPU XPlane trace) and a span in the
    telemetry sink (``id``/``parent``/``t0`` — the same sink request
    tracing writes to, so ``export_chrome_tracing`` shows engine phases
    and per-request spans on one timeline). ``phase=`` also observes the
    span's duration into ``serving.phase_s{phase=...}``: the phase and the
    span are one clock read, not two timings of almost the same region.
    ``with annotate(...) as sp`` yields the sink span (``sp.set(k=v)`` adds
    args learned inside, ``sp.event(name)`` an instant under its trace).
    The sink write and the phase are skipped when ``FLAGS_telemetry`` is
    off and no ``Profiler`` is running; the XLA annotation always
    applies. Names starting ``serving.`` are what the benchmark's trace
    loader keeps of the program's host annotations."""

    __slots__ = ("_ann", "_span", "_phase")

    def __init__(self, name, phase=None, **span_args):
        self._ann = TraceAnnotation(name)
        self._span = (telemetry.tracer().span(name, **span_args)
                      if _recording() else telemetry.NOOP_SPAN)
        self._phase = phase

    def __enter__(self):
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        if self._phase is not None and self._span is not telemetry.NOOP_SPAN:
            perfwatch.observe_phase(self._phase, self._span.dur)
        return False


def record_span(name, t0, dur_s, phase=None, parent=0, **span_args):
    """``annotate`` for an interval that is only known once it is over
    (the host gap between two dispatches, a stalled decode): a sink span
    from ``t0`` (``time.monotonic()``) lasting ``dur_s``, observed into
    ``serving.phase_s{phase}`` like a ``with`` block's. No XLA annotation
    can be written after the fact."""
    if not _recording():
        return
    telemetry.tracer().add_span(name, None, dur_s, t0=t0, parent=parent,
                                **span_args)
    if phase is not None:
        perfwatch.observe_phase(phase, dur_s)


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    CUSTOM_DEVICE = "custom_device"
    TPU = "tpu"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class RecordEvent:
    """Host-side span (reference python/paddle/profiler/utils.py
    RecordEvent; C++ paddle/fluid/platform/profiler/host_tracer.cc):
    ``annotate`` with explicit ``begin()``/``end()``."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._scope = None

    def begin(self):
        self._scope = annotate(self.name)
        self._scope.__enter__()

    def end(self):
        scope, self._scope = self._scope, None
        if scope is not None:
            scope.__exit__(None, None, None)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Step-state scheduler (reference profiler.py make_scheduler)."""
    period = closed + ready + record

    def schedule(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = (step - skip_first) % max(period, 1)
        if repeat and (step - skip_first) // max(period, 1) >= repeat:
            return ProfilerState.CLOSED
        if s < closed:
            return ProfilerState.CLOSED
        if s < closed + ready:
            return ProfilerState.READY
        if s == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


class Profiler:
    """Reference python/paddle/profiler/profiler.py:358. ``start``/``stop``
    wrap ``jax.profiler.start_trace``/``stop_trace`` (XPlane → TensorBoard/
    Perfetto); host spans go to the telemetry sink for chrome export,
    also with ``FLAGS_telemetry=0`` while the session runs."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, profile_memory=False, with_flops=False,
                 log_dir=None, python_tracer=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        # where the XPlane dump goes: this argument, else
        # $PADDLE_PROFILER_LOGDIR, else a fresh temporary directory a
        # session (``log_dir`` says which after ``start()``)
        self._log_dir = log_dir
        # the Python tracer floods a serving trace and slows the host it
        # measures: off unless asked for
        self.python_tracer = bool(python_tracer)
        self._step = 0
        self._tracing = False
        self._traced = False
        self._step_times = []
        self._last_step_t = None
        self._t_start_wall = None

    def start(self):
        global _active
        _active = True
        # session window anchor: export() filters the (process-lifetime)
        # telemetry sink to spans recorded after this point, so a
        # profile of one step is not dominated by pre-session serving
        # spans already in the ring
        self._t_start_wall = time.time()  # wall-clock: x-process trace epoch
        self._last_step_t = time.perf_counter()
        if not self.timer_only:
            self._log_dir = (self._log_dir
                             or os.environ.get("PADDLE_PROFILER_LOGDIR")
                             or tempfile.mkdtemp(prefix="paddle_tpu_profile_"))
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 1 if self.python_tracer else 0
            try:
                jax.profiler.start_trace(self._log_dir,
                                         profiler_options=options)
                self._tracing = self._traced = True
            except Exception:
                self._tracing = False
        return self

    @property
    def log_dir(self):
        """The directory of this session's XPlane dump (None before
        ``start()`` and for a ``timer_only`` session)."""
        return self._log_dir

    def stop(self):
        global _active
        _active = False
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return "no steps recorded"
        import numpy as np

        arr = np.asarray(self._step_times)
        return (f"avg step {arr.mean()*1e3:.2f}ms "
                f"(min {arr.min()*1e3:.2f}, max {arr.max()*1e3:.2f}, "
                f"n={len(arr)})")

    def device_time(self):
        """This session's device seconds by program and, per program, by
        pass and by scope: ``attribute_device_time`` over the newest
        ``.xplane.pb`` under ``log_dir``. None where the session traced
        nothing or the trace holds no device plane (a CPU)."""
        if not self._traced or self._tracing:
            return None
        paths = sorted(glob.glob(os.path.join(
            self._log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        events = device_op_seconds(paths[-1]) if paths else []
        if not events:
            return None
        totals: dict = {}
        for program, _, seconds in events:
            totals[program] = totals.get(program, 0.0) + seconds
        split = attribute_device_time(events)
        return {p: {"seconds": s, "split": split[p]}
                for p, s in totals.items()}

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """The reference's "Model Summary" (forward / backward /
        optimization / others) for a TPU: device seconds by compiled
        program, and for a program the table knows by pass and by scope."""
        print(self.step_info())
        programs = self.device_time()
        if programs is None:
            where = "timer only" if self.timer_only else jax.default_backend()
            print(f"no device plane in this session's trace ({where}): "
                  "device time by program, pass and scope needs a TPU trace")
            return None

        def shares(table, total):
            return "  ".join(
                f"{k or '(no scope)'} {100.0 * v / total:.1f} %"
                for k, v in sorted(table.items(), key=lambda kv: -kv[1]))

        print("device time by program (self seconds of its HLO operations):")
        for program, rec in sorted(programs.items(),
                                   key=lambda kv: -kv[1]["seconds"]):
            print(f"  {program:<28s} {rec['seconds']:10.6f} s")
            split = rec["split"]
            if split is None:
                print("    not in the program table (note_program)")
                continue
            total = split["seconds"] or 1.0
            print(f"    by pass:  {shares(split['by_pass'], total)}")
            if op_detail:
                print(f"    by scope: {shares(split['by_scope'], total)}")
            print("    " + shares({
                "compiler clones": split["compiler_clone"],
                "in fusions that mix scopes or passes": split["mixed"],
                "unmatched": split["unmatched"]}, total))
        return programs

    def export(self, path, format="json"):
        # scoped to THIS profiler session (start() → now); the
        # module-level export_chrome_tracing dumps the whole sink
        return export_chrome_tracing(
            path, since_wall_s=getattr(self, "_t_start_wall", None))

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def _sink_events(since_wall_s=None):
    evs = telemetry.tracer().spans()
    if since_wall_s is not None:
        cut = since_wall_s * 1e6
        evs = [e for e in evs if e.get("ts", 0) >= cut]
    return evs


def export_chrome_tracing(path, dir_name=None, since_wall_s=None):
    """Dump the telemetry span sink (request-trace spans, ``annotate``
    scopes, RecordEvent spans) as ONE chrome://tracing JSON (reference
    chrometracing_logger.cc analog; the device timeline lives in the
    XPlane dump under the jax.profiler log dir). ``since_wall_s`` restricts sink events to those recorded at
    or after that wall-clock time (``Profiler.export`` passes its
    session start, so one profiled step is not dominated by pre-session
    serving spans). The file round-trips through
    :func:`load_profiler_result`."""
    evs = _sink_events(since_wall_s)
    with open(path, "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
    return path


class ProfilerResult(dict):
    """A loaded trace: a plain dict (``result["traceEvents"]`` — the
    historical surface) plus span accessors, so exported profiles
    round-trip as REAL span data, not an opaque blob."""

    @property
    def events(self) -> list:
        return self.get("traceEvents", [])

    def spans(self, name=None, trace=None) -> list:
        """Complete (``ph == "X"``) spans, optionally filtered by name
        and/or by the trace id carried in ``args`` (including batched
        spans whose ``args['traces']`` list contains it)."""
        out = [e for e in self.events if e.get("ph") == "X"]
        if name is not None:
            out = [e for e in out if e.get("name") == name]
        if trace is not None:
            out = [e for e in out
                   if e.get("args", {}).get("trace") == trace
                   or trace in (e.get("args", {}).get("traces") or ())]
        return out

    def span_names(self) -> set:
        return {e.get("name") for e in self.events}

    def total_dur_us(self, name) -> float:
        return sum(e.get("dur", 0.0) for e in self.spans(name))

    def save(self, path) -> str:
        with open(path, "w") as f:
            json.dump(dict(self), f)
        return path


def load_profiler_result(path) -> ProfilerResult:
    with open(path) as f:
        return ProfilerResult(json.load(f))
