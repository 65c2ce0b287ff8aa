"""The traced run: the program's profiler around the end of the window.

``--trace 1`` traces the last seconds of the measured window and stops the
trace after the window has closed, so that writing it out stalls nothing that
is measured. It calls ``jax.profiler`` itself, with the Python tracer off:
``paddle_tpu.profiler.Profiler`` wraps the same call but cannot switch that
tracer off (it floods a serving trace and slows the host it measures) and
defaults its directory to a fixed path under ``/tmp`` (``PERF.md``, Open
questions). The harness's own spans (``bench.step``, ``bench.submit``,
``bench.wait_arrival``) ride into the same trace beside the engine's
``serving.*`` annotations. ``reduce`` turns the ``.xplane.pb`` into events and
into what the result line carries (``trace_reduce.py``).
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import time

from benchmark import trace_reduce


class Tracer:
    def __init__(self, workdir: str, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(workdir, "trace")
        self._profiler = None
        self.t_start = self.t_stop = None

    def span(self, name: str):
        if self._profiler is None:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)

    def start_once(self):
        if not self.enabled or self._profiler is not None:
            return
        import jax.profiler

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._profiler = jax.profiler
        self.t_start = time.monotonic()

    def stop(self):
        if self._profiler is None or self.t_stop is not None:
            return
        self.t_stop = time.monotonic()
        self._profiler.stop_trace()

    def reduce(self, bag: dict):
        """None when nothing was traced; else busy/window seconds, the
        breakdown, and ``bag["trace_events"]`` for the per-layer readers."""
        if self._profiler is None:
            return None
        paths = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        events = trace_reduce.load_xplane(sorted(paths)[-1])
        bag["trace_events"] = events
        bag["trace_host_span"] = (self.t_start, self.t_stop)
        out = trace_reduce.summary(events, bag["chips"])
        shutil.rmtree(self.dir, ignore_errors=True)
        return out
