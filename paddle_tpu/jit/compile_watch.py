"""Compile watchdog: production monitoring of XLA backend compiles.

PR 5's serving contract is ZERO post-warmup XLA compiles — a single
recompile on the hot path costs more wall time than thousands of decode
segments, and until now the invariant was asserted by exactly one
test-local listener (``tests/test_serving_pipeline.py``) and never
monitored in production. This module promotes that listener into the jit
layer:

* :class:`CompileWatchdog` (one per process, ``compile_watchdog()``)
  registers a ``jax._src.monitoring`` duration listener for
  ``/jax/core/compile/backend_compile_duration`` and counts every
  backend compile into ``xla.compiles_total{phase=...}``, adds the
  seconds JAX reports to ``xla.compile_s_total{phase=...}`` and records
  the compile as a span ``xla.compile`` in the telemetry sink (``phase``,
  ``program`` = the jitted function's name, ``cached`` = served by the
  persistent compile cache, ``key`` = the dispatch context's program
  where there is one; its parent is the span open on the thread, e.g.
  the ``serving.warmup_program`` it ran in):

  - ``warmup`` — inside a :meth:`warmup_scope` (the engine's AOT
    ``warmup()``), or any compile before the first warmup completed
    (model build, program construction);
  - ``serving`` — inside a :meth:`dispatch_context` (the engine wraps
    every non-AOT program dispatch in one) AFTER warmup armed the
    watchdog: a POST-WARMUP RECOMPILE, the invariant violation. The
    event also lands in the flight recorder and triggers a post-mortem
    dump NAMING the recompiled program and its traced shapes (the
    listener itself only learns "a compile happened" from jax — the
    dispatch context carries the who);
  - ``other`` — armed, but outside any serving dispatch (a training
    step compiling in the same process is not a serving regression).

* :func:`count_backend_compiles` — the shared test/bench utility (the
  promoted form of the inline listener): a context manager yielding the
  list of compile durations observed in its scope.

The listener is passive and cheap (two string compares per jax event);
counting/dumping is additionally gated on ``FLAGS_telemetry``. It is
registered when ``paddle_tpu.jit`` is imported, so that the compiles of a
model's build and of a train step are seen too.
"""
from __future__ import annotations

import contextlib
import threading
import time

from ..core import telemetry

__all__ = ["CompileWatchdog", "compile_watchdog",
           "count_backend_compiles", "BACKEND_COMPILE_EVENT"]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fires just before BACKEND_COMPILE_EVENT, on the same thread, when the
# persistent compile cache served the executable
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_M_COMPILES = telemetry.counter(
    "xla.compiles_total", "XLA backend compiles by phase: warmup (AOT "
    "warmup scopes + pre-warmup build), serving (a POST-WARMUP RECOMPILE "
    "on the engine dispatch path — dumps the flight recorder naming the "
    "program), other (armed process, non-serving compile)")
_M_COMPILE_S = telemetry.counter(
    "xla.compile_s_total", "seconds of XLA backend compiles as JAX "
    "reports them (a persistent-cache hit's retrieval included), by the "
    "phases of xla.compiles_total")


def _monitoring():
    from jax._src import monitoring

    return monitoring


class CompileWatchdog:
    """Process-wide compile counter + post-warmup recompile alarm."""

    def __init__(self):
        self._lock = threading.Lock()
        self._registered = False
        self._armed = False          # a warmup completed: serving began
        self._local = threading.local()

    # ----------------------------------------------------------- lifecycle

    def start(self):
        """Register the jax monitoring listener (idempotent)."""
        with self._lock:
            if self._registered:
                return self
            _monitoring().register_event_duration_secs_listener(
                self._on_event)
            self._registered = True
        return self

    def stop(self):
        """Unregister (tests); counters keep their values."""
        with self._lock:
            if not self._registered:
                return
            _monitoring().unregister_event_duration_listener(self._on_event)
            self._registered = False

    def reset(self):
        """Disarm (tests): compiles count as ``warmup`` again until the
        next :meth:`arm`. Counter values are cleared by
        ``telemetry.reset_telemetry()``, not here."""
        self._armed = False

    def arm(self):
        """Warmup is done: from now on a compile inside a serving
        dispatch context is a recompile incident."""
        self._armed = True

    @property
    def armed(self) -> bool:
        return self._armed

    # ------------------------------------------------------------- scopes

    @contextlib.contextmanager
    def warmup_scope(self):
        """Compiles inside count as ``phase="warmup"`` even when the
        watchdog is armed (a ``scale_out`` replica warming while the
        fleet serves is not an incident)."""
        depth = getattr(self._local, "warm", 0)
        self._local.warm = depth + 1
        try:
            yield
        finally:
            self._local.warm = depth

    @contextlib.contextmanager
    def dispatch_context(self, program, **detail):
        """Names the serving program being dispatched on this thread so
        a compile fired inside can be attributed — the engine wraps its
        non-AOT dispatches (``program`` is the executable-cache key,
        ``detail`` carries the traced shapes)."""
        prev = getattr(self._local, "ctx", None)
        self._local.ctx = {"program": str(program), **detail}
        try:
            yield
        finally:
            self._local.ctx = prev

    # ------------------------------------------------------------ listener

    def thread_compiles(self) -> tuple:
        """``(n, cached)``: backend compiles this thread has seen since the
        listener was registered, and whether the newest was served by the
        persistent compile cache. A ``lower().compile()`` that leaves
        ``n`` as it was came out of JAX's in-process cache."""
        return (getattr(self._local, "n", 0),
                getattr(self._local, "last_cached", False))

    def _on_event(self, event, duration, **kw):
        if event == CACHE_HIT_EVENT:
            self._local.hit = True
            return
        if event != BACKEND_COMPILE_EVENT:
            return
        cached, self._local.hit = getattr(self._local, "hit", False), False
        self._local.last_cached = cached
        self._local.n = getattr(self._local, "n", 0) + 1
        if not telemetry.enabled():
            return
        ctx = getattr(self._local, "ctx", None)
        if getattr(self._local, "warm", 0) > 0 or not self._armed:
            phase = "warmup"
        elif ctx is None:
            phase = "other"
        else:
            phase = "serving"
        _M_COMPILES.inc(phase=phase)
        _M_COMPILE_S.inc(duration, phase=phase)
        args = {"key": ctx["program"]} if ctx is not None else {}
        telemetry.tracer().add_span(
            "xla.compile", None, duration, t0=time.monotonic() - duration,
            parent=telemetry.current_span_id(), phase=phase,
            program=str(kw.get("fun_name", "")), cached=cached, **args)
        if phase != "serving":
            return
        # a post-warmup recompile is a post-mortem moment: the program
        # name + traced shapes are exactly what the operator needs to
        # add the missing bucket/width/segment to warmup()
        telemetry.flight_dump("recompile", seconds=round(duration, 4),
                              **ctx)


_watchdog = CompileWatchdog()


def compile_watchdog() -> CompileWatchdog:
    return _watchdog


@contextlib.contextmanager
def count_backend_compiles():
    """Yield a list that accumulates the duration of every XLA backend
    compile observed in the scope — the one listener implementation
    tests and benches share (``assert not compiles`` is the zero-compile
    invariant)."""
    events = []

    def listener(event, duration, **kw):
        if event == BACKEND_COMPILE_EVENT:
            events.append(duration)

    mon = _monitoring()
    mon.register_event_duration_secs_listener(listener)
    try:
        yield events
    finally:
        mon.unregister_event_duration_listener(listener)
