"""From a profiler trace (``.xplane.pb``) to events, and from events to
busy time, idle gaps, program and kernel times.

Two stages, so that the second can be checked on a small recorded trace
(``tests/data/recorded_trace.json.gz``) without the profiler:

1. ``load_xplane`` reads the planes with ``jax.profiler.ProfileData`` and
   keeps three kinds of event, times in seconds from the trace's first event:
   ``op`` — one HLO operation on a device (the TPU planes' "XLA Ops" line),
   named ``<jitted program>:<HLO op>`` by the "XLA Modules" event around it;
   ``program`` — one execution of a jitted program on a device;
   ``host`` — a ``TraceAnnotation`` on a host thread (the engine's
   ``serving.*`` scopes and the harness's ``bench.*`` spans).
2. everything else is arithmetic on those events.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LINES = {"XLA Ops": "op", "XLA Modules": "program"}
HOST_PREFIXES = ("bench.", "serving.")
HLO_CHARS = 480           # of an operation's text kept for pattern matching
CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def program_name(module_event_name: str) -> str:
    """``jit_segment(123456)`` -> ``jit_segment``."""
    return re.sub(r"\(\d+\)$", "", module_event_name.strip())


def op_name(event_name: str) -> str:
    """The HLO op's own name: ``%fusion.12 = bf16[..] fusion(...)`` and
    ``fusion.12`` both give ``fusion.12``."""
    return event_name.strip().lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def hlo_text(event_name: str) -> str:
    """The operation's text without layouts and operand names, cut short:
    what a metric's pattern is matched against, e.g.
    ``closed_call = bf16[8,32,128] custom-call(s32[8,8], s32[8], ...),
    custom_call_target="tpu_custom_call"``."""
    text = re.sub(r"\{[^{}]*\}", "", event_name.strip().lstrip("%"))
    text = re.sub(r" %[\w.\-]+", "", text)
    if MOSAIC in text and len(text) > HLO_CHARS:
        return text[:HLO_CHARS - len(MOSAIC) - 2] + ", " + MOSAIC
    return text[:HLO_CHARS]


def load_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw = []
    for plane in data.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name in LINES:
                for e in line.events:
                    raw.append((LINES[line.name], int(dev.group(1)), e.name,
                                e.start_ns, e.duration_ns))
            elif not dev and plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        raw.append(("host", -1, e.name, e.start_ns,
                                    e.duration_ns))
    if not raw:
        return []
    t0 = min(r[3] for r in raw)
    events = [{"kind": k, "device": d, "name": n, "start": (s - t0) * 1e-9,
               "dur": dur * 1e-9} for k, d, n, s, dur in raw]
    return name_ops(events)


def name_ops(events: list[dict]) -> list[dict]:
    """Give every device operation its short name, its text, the program
    that encloses it on its device, and its self time (a ``while`` holds
    the operations of its body: their time is theirs, not the loop's)."""
    by_dev: dict = {}
    for e in events:
        if e["kind"] == "program":
            e["program"] = program_name(e["name"])
            by_dev.setdefault(e["device"], []).append(e)
    for progs in by_dev.values():
        progs.sort(key=lambda e: e["start"])
    starts = {d: [p["start"] for p in progs] for d, progs in by_dev.items()}
    for e in events:
        if e["kind"] != "op":
            continue
        e["op"] = op_name(e["name"])
        e["hlo"] = hlo_text(e["name"])
        progs = by_dev.get(e["device"], [])
        i = bisect.bisect_right(starts.get(e["device"], []), e["start"]) - 1
        prog = "?"
        if i >= 0 and e["start"] < progs[i]["start"] + progs[i]["dur"]:
            prog = progs[i]["program"]
        e["program"] = prog
        e["name"] = f"{prog}:{e['op']}"
    for d in {e["device"] for e in events if e["kind"] == "op"}:
        ops = sorted((e for e in events
                      if e["kind"] == "op" and e["device"] == d),
                     key=lambda e: (e["start"], -e["dur"]))
        stack: list = []
        for e in ops:
            e["self"] = e["dur"]
            while stack and e["start"] >= stack[-1]["start"] \
                    + stack[-1]["dur"]:
                stack.pop()
            if stack:
                stack[-1]["self"] -= e["dur"]
            stack.append(e)
    return events


def save_events(events, path):
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load_events(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


# --------------------------------------------------------------- arithmetic

def union(intervals) -> list[tuple[float, float]]:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def device_ops(events, device=None):
    return [e for e in events if e["kind"] == "op"
            and (device is None or e["device"] == device)]


def devices(events):
    return sorted({e["device"] for e in events if e["kind"] == "op"})


def traced_window(events):
    """From the first to the last device operation of the trace."""
    ops = device_ops(events)
    if not ops:
        return None
    return min(e["start"] for e in ops), max(e["start"] + e["dur"]
                                             for e in ops)


def busy_intervals(events, device):
    return union((e["start"], e["start"] + e["dur"])
                 for e in device_ops(events, device))


def busy_seconds(events) -> float:
    """Seconds in which an operation ran, averaged over the devices used."""
    devs = devices(events)
    if not devs:
        return 0.0
    return sum(length(busy_intervals(events, d)) for d in devs) / len(devs)


def top_ops(events, n=10):
    """Device operations by total self time, averaged over the devices
    used."""
    total: dict = {}
    for e in device_ops(events):
        total[e["name"]] = total.get(e["name"], 0.0) + e["self"]
    nd = max(len(devices(events)), 1)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, secs / nd] for name, secs in top]


def idle_gaps(events, n=10):
    """The longest gaps with no operation on the first device, each named by
    the innermost host span open at its middle."""
    devs = devices(events)
    if not devs:
        return []
    busy = busy_intervals(events, devs[0])
    host = [e for e in events if e["kind"] == "host"]
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])]
    out = []
    for dur, s, e in sorted(gaps, reverse=True)[:n]:
        mid = (s + e) / 2
        open_ = [h for h in host if h["start"] <= mid < h["start"] + h["dur"]]
        name = min(open_, key=lambda h: h["dur"])["name"] if open_ \
            else "no host span"
        out.append([name, dur])
    return out


def summary(events, chips: int) -> dict:
    win = traced_window(events)
    if win is None:
        raise RuntimeError("the trace holds no device operation: the traced "
                           "window ran nothing on the device")
    return {"busy_s": busy_seconds(events), "window_s": win[1] - win[0],
            "breakdown": {"device_ops": top_ops(events),
                          "idle_gaps": idle_gaps(events)}}


def program_seconds(events, pattern: str, device=None):
    """(executions, device seconds) of programs whose name matches: the
    union of their operations' intervals, so waits between a program's
    operations are not counted."""
    rx = re.compile(pattern)
    devs = devices(events) if device is None else [device]
    secs = 0.0
    for d in devs:
        secs += length(union((e["start"], e["start"] + e["dur"])
                             for e in device_ops(events, d)
                             if rx.search(e["program"])))
    runs = sum(1 for e in events if e["kind"] == "program"
               and rx.search(e["program"])
               and (device is None or e["device"] == device))
    nd = max(len(devs), 1)
    return runs / nd, secs / nd


def op_seconds(events, pattern: str, program: str = ""):
    """(calls, device seconds) of operations whose text (``hlo_text``)
    matches, inside programs whose name matches; averaged over the devices
    used."""
    rx, prx = re.compile(pattern), re.compile(program)
    hits = [e for e in device_ops(events)
            if rx.search(e["hlo"]) and prx.search(e["program"])]
    nd = max(len(devices(events)), 1)
    return len(hits) / nd, sum(e["dur"] for e in hits) / nd
