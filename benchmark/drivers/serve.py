"""Serving driver: ``ServingFrontend.submit``/``step`` under generated traffic.

Set-up is build, ``warmup()`` and a ramp of the same traffic that is not
measured; the window then opens on an engine at its steady occupancy, arrivals
go on to the window's end and nothing is drained inside it. The harness stamps
what the client sees on its own monotonic clock: a request is due at its
scheduled time, and a token is seen when the ``step()`` that produced it
returns. After the window closes the requests in flight are pumped to their
end (their first tokens complete the TTFT sample; nothing seen after the close
counts as served), the peak memory is read, the program's state is freed, and
the plain reference judges a seeded sample of the finished requests.

The model is the configuration's: its ``program`` module builds the model and
the serving stack, its ``compare`` module holds the reference's judgement
(``harness.module_of``), and what the program module's ``bag_extras`` gives
goes into the bag for the readers.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from benchmark import harness, stats, tracing

DRAIN_LIMIT_S = 60.0


def _snapshot():
    from paddle_tpu.core import telemetry

    return telemetry.registry().snapshot()


class Session:
    """One frontend under one list of requests: what was sent, what was
    seen, and the pool's and the slots' state after every turn."""

    def __init__(self, fe, engine, requests, span):
        self.fe, self.engine, self.requests = fe, engine, requests
        self.span = span
        self.stamps = {r["rid"]: stats.Stamp(r["rid"], len(r["prompt"]),
                                             r["max_new"], None)
                       for r in requests}
        self.samples: list = []
        self.sent = 0
        self.t_ramp = None
        self.longest_turn = 0.0

    def start(self):
        self.t_ramp = time.monotonic()
        for r in self.requests:
            self.stamps[r["rid"]].due = self.t_ramp + r["due_s"]
        return self.t_ramp

    def _send_due(self, now):
        fe = self.fe
        while self.sent < len(self.requests):
            r = self.requests[self.sent]
            if self.t_ramp + r["due_s"] > now:
                break
            self.stamps[r["rid"]].submitted = time.monotonic()
            fe.submit(r["prompt"], max_new_tokens=r["max_new"], rid=r["rid"])
            self.sent += 1

    def pump(self, t_end, on_tick=None):
        """Send what is due and turn the scheduler until ``t_end``; with
        ``t_end`` None send nothing more and turn until nothing is pending
        (bounded by ``DRAIN_LIMIT_S``)."""
        fe, engine = self.fe, self.engine
        t_give_up = time.monotonic() + DRAIN_LIMIT_S
        while True:
            now = time.monotonic()
            if now >= (t_give_up if t_end is None else t_end):
                break
            if on_tick is not None:
                on_tick(now)
            if t_end is not None:
                with self.span("bench.submit"):
                    self._send_due(now)
            if fe.pending() or engine.has_work():
                with self.span("bench.step"):
                    fe.step()
                self.longest_turn = max(self.longest_turn,
                                        time.monotonic() - now)
                self.observe()
            elif t_end is None:
                break
            else:
                nxt = self.sent
                due = (self.t_ramp + self.requests[nxt]["due_s"]
                       if nxt < len(self.requests) else t_end)
                with self.span("bench.wait_arrival"):
                    time.sleep(max(0.0, min(due, t_end) - time.monotonic()))

    def observe(self):
        """Stamp what this turn showed: new tokens of live requests and
        terminal results."""
        now = time.monotonic()
        live_tokens = active = 0
        for rid, (_, toks) in self.fe.progress().items():
            n = len(toks)
            st = self.stamps[rid]
            if n and (not st.seen or st.seen[-1][1] < n):
                st.seen.append((now, n))
            if n:
                active += 1
                live_tokens += st.prompt_len + n
        for rid, res in self.fe.results().items():
            st = self.stamps[rid]
            st.status = res.status
            st.n_final = len(res.tokens)
            st.tokens = np.asarray(res.tokens)
            if st.n_final and (not st.seen or st.seen[-1][1] < st.n_final):
                st.seen.append((now, st.n_final))
        kv = self.engine.kv_stats()
        self.samples.append((now, kv["slot_occupancy"], kv["pages_granted"],
                             kv["pages_total"], live_tokens, active,
                             self.fe.pending()))


def run(ctx: dict) -> dict:
    import paddle_tpu as paddle

    log, config, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    program = harness.module_of(config, "program")
    compare = harness.module_of(config, "compare")
    m = config["model"]
    seconds = ctx["seconds"]
    kind = ctx["device"]["kind"]
    log(f"compile cache at {paddle.jit.enable_compilation_cache()} ({kind})")

    t0 = time.monotonic()
    model = program.build_model(config, ctx["seed"])
    engine, fe = program.build_serving(model, config)
    info = fe.warmup()
    log(f"built and warmed {info['programs']} programs "
        f"({info['cached']} cached) in {time.monotonic() - t0:.1f} s on {kind}")

    gen = importlib.import_module(traffic["generator"])
    requests = gen.generate(traffic, ctx["seed"], seconds, m["vocab_size"])
    tracer = tracing.Tracer(ctx["workdir"], ctx["trace"])
    ses = Session(fe, engine, requests, tracer.span)

    # ---- ramp (set-up), then the window
    t_open = ses.start() + float(traffic["ramp_s"])
    t_close = t_open + seconds
    ses.pump(t_open)
    snap0 = _snapshot()
    # the trace starts one (longest seen) turn early: a turn that straddles
    # the planned start would otherwise leave the traced run with no trace
    trace_s = min(float(traffic.get("trace_s", 5.0)), seconds)
    ses.pump(t_close, on_tick=lambda now: tracer.start_once()
             if now + ses.longest_turn >= t_close - trace_s else None)
    snap1 = _snapshot()
    tracer.stop()
    sent, stamps, samples = ses.sent, ses.stamps, ses.samples

    # ---- past the close: finish what is in flight, stamp nothing as served
    t_drain = time.monotonic()
    ses.pump(None)
    log(f"window {seconds} s, {sent} requests sent, drained in "
        f"{time.monotonic() - t_drain:.1f} s on {kind}")

    peak = harness.memory_peak_bytes(ctx["chips"])
    sent_stamps = [stamps[r["rid"]] for r in requests[:sent]]
    failed = [st for st in sent_stamps if st.status != "ok"]
    for st in failed[:5]:
        log(f"request {st.rid} ended {st.status!r} on {kind}")

    # ---- end-to-end, on the window [t_open, t_close)
    w0, w1 = t_open, t_close
    window = w1 - w0
    prompt_tok, out_tok = stats.serve_tokens(sent_stamps, w0, w1)
    e2e = {"setup_s": t_open - ctx["t_process"],
           "serve_tok_s": (prompt_tok + out_tok) / window}
    tpot = stats.tpot_mean_ms(sent_stamps, w0, w1)
    if tpot is not None:
        e2e["tpot_mean_ms"] = tpot
    ttft = stats.ttft_each_ms(sent_stamps, w0, w1)
    if ttft:
        e2e["ttft_p50_ms"] = stats.percentile(ttft, 50)

    compiles = _counter_delta(snap0, snap1, "xla.compiles_total")
    preempt = _counter_delta(snap0, snap1, "serving.preemptions")
    log(f"compiles in the window {compiles}, preemptions {preempt}, "
        f"prompt tokens {prompt_tok}, output tokens {out_tok}, failed "
        f"{len(failed)} of {sent} on {kind}")

    # ---- free the program, then judge a sample by the plain reference
    sample = compare.pick_sample(sent_stamps, requests, w0, w1,
                                 int(traffic["check_requests"]), ctx["seed"])
    del fe, engine, model, ses
    gc.collect()
    t_ref = time.monotonic()
    checks, detail = compare.serving_checks(
        config, ctx["seed"], sample, ctx["limits"],
        control_mm=ctx.get("control"))
    short = sum(1 for st in sent_stamps
                if st.status == "ok" and st.n_final != st.max_new)
    checks.append(("wrong_length_requests", float(short), 0.0))
    log(f"reference over {len(sample)} requests, {detail['tokens']} tokens, "
        f"took {time.monotonic() - t_ref:.1f} s on {kind}")

    bag = {
        "kind": "serve", "model": m, "config": config, "chips": ctx["chips"],
        "device_kind": kind, "stamps": sent_stamps, "window": (w0, w1),
        "samples": [s for s in samples if w0 <= s[0] < w1],
        "snap0": snap0, "snap1": snap1, "end_to_end": e2e,
        "prompt_tokens": prompt_tok, "output_tokens": out_tok,
        "attempted": sent, "failed": len(failed), "checks": checks,
        "memory_peak_bytes": peak,
        "notes": {"compiles_in_window": compiles, "preemptions": preempt,
                  "requests_due_in_window": len(ttft),
                  "reference": detail, "device": kind},
    }
    bag["trace"] = tracer.reduce(bag)
    extras = getattr(program, "bag_extras", None)
    if extras is not None:
        bag.update(extras(config))
    return bag


def _counter_delta(snap0, snap1, name):
    def total(snap):
        return sum(v for k, v in (snap.get("counters") or {}).items()
                   if k == name or k.startswith(name + "{"))
    return total(snap1) - total(snap0)
