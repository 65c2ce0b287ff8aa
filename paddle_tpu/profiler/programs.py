"""Which scope and which pass each HLO instruction of a compiled program
came from, kept past the life of whoever compiled it.

A device trace names an operation by its HLO instruction (``fusion.2244``,
``iota_reduce_fusion.2``) under its module (``jit_one_step``,
``jit_segment``) and carries nothing of where it came from. The optimized
module's text does: every instruction's ``metadata={op_name="..."}`` is the
path JAX traced it under, ``jax.named_scope`` names and transform wrappers
in one string. ``note_program`` keeps a compiled program's host-side
modules (no device memory, nothing parsed), ``program_ops`` parses on the
first question, and ``attribute_device_time`` is the one join of (program,
instruction, seconds) against the table: the benchmark's reader
(``benchmark/readers/scopes.py``) and ``Profiler.summary()`` both call it.

Two call sites register: ``ContinuousBatchingEngine._warmup_compile`` and
the compiling call of ``jit.TrainStep.__call__``. Nothing here runs on a
dispatch path.
"""
from __future__ import annotations

import collections
import re
import threading
import time
import weakref

from ..core import telemetry

__all__ = ["ProgramTable", "note_program", "program_ops",
           "attribute_device_time", "classify", "program_table",
           "device_op_seconds", "self_seconds"]

# ---------------------------------------------------------------- the rule
#
# Data, pinned by tests/test_program_ops.py from what ``as_text()`` of the
# real train step and the real segment program print under jax 0.9.
#
# A path is ``jit(one_step)/transpose(jvp(jvp()))/checkpoint/
# rematted_computation/attn/jit(run)/fused_rope/while/body/mul``: its last
# component is the primitive; ``name(...)`` components are transforms whose
# argument is the path that was open when the transform began (``jvp(attn)``
# holds the scope ``attn``; ``jit(run)`` holds a function's name, no scope);
# the bare components below are JAX's own; what is left is a named scope or
# a ``pallas_call``'s ``name=``, outermost first.

# transforms whose argument is a path that may hold scopes
TRANSFORMS = ("jvp", "transpose", "vmap", "pmap", "shard_map",
              "custom_jvp", "custom_vjp")
# wrappers whose argument names a function, never a scope
CALLS = ("jit", "pjit")
# JAX's own components of a path
BARE = frozenset({
    "while", "body", "cond", "checkpoint", "rematted_computation",
    "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "remat", "remat2", "pjit", "shard_map", "pallas_call",
})
BRANCH = re.compile(r"^branch_\d+_fun$")

# the first token a path holds decides its pass
PASS_TOKENS = (("recompute", "rematted_computation"),
               ("backward", "transpose("),
               ("forward", "jvp("))
PASSES = ("forward", "backward", "recompute", "none")
# XLA's own rematerialization clones an instruction under such a name
CLONE_TOKEN = ".remat"

UNSCOPED = ""        # the outermost scope of an instruction that has none

_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$", re.S)


def _split(path: str) -> list:
    """Components of a path: ``/`` outside parentheses separates."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def _scopes_of(components, out):
    for comp in components:
        if not comp or comp in BARE or BRANCH.match(comp):
            continue
        m = _WRAPPED.match(comp)
        if m is None:
            out.append(comp)
        elif m.group(1) in TRANSFORMS:
            _scopes_of(_split(m.group(2)), out)
        elif m.group(1) not in CALLS:
            out.append(comp)      # a scope a user named ``f(x)``
    return out


def classify(op_name: str, instruction: str = "") -> dict:
    """``{"path", "scopes", "pass", "compiler_clone", "mixed"}`` of one
    instruction from its ``op_name`` metadata and its own name (``mixed``
    is ``parse_hlo_text``'s to set: it takes a fusion's body). Fusions
    that XLA merged carry ``a;b``: the first path files the instruction."""
    path = op_name.split(";", 1)[0]
    comps = _split(path)
    scopes = tuple(_scopes_of(comps[:-1], []))
    pass_ = next((p for p, tok in PASS_TOKENS if tok in path), "none")
    return {"path": path, "scopes": scopes, "pass": pass_,
            "compiler_clone": CLONE_TOKEN in instruction, "mixed": False}


# ``  ROOT %fusion.12 = bf16[..] fusion(...), ..., metadata={op_name="..."``
# is an instruction; ``%fused_computation.3 (p: f32[]) -> f32[] {`` opens a
# computation
_LINE = re.compile(
    r"^(?:  (?:ROOT )?%?(?P<instr>[\w.\-]+) = "
    r"|(?:ENTRY )?%?(?P<comp>[\w.\-]+) \(.*\{$)", re.M)
_OP_NAME = 'op_name="'
_FUSION, _CALLS = " fusion(", ", calls="
_NAME = re.compile(r"%?([\w.\-]+)")


def parse_hlo_text(text: str) -> dict:
    """Every instruction of every computation of a module's text, by the
    name the device trace prints: ``{instruction: record}``. An instruction
    without metadata (a parameter, what XLA inserted) has no scope and no
    pass. A fusion is ONE instruction under its own metadata, whatever it
    fused: where its body holds instructions of another scope or pass, its
    record says ``mixed`` (the optimizer's update in the epilogue of a
    weight-gradient product, a recomputed activation inside the backward
    fusion that reads it), and its time is still all filed under its own."""
    ops, by_path, bodies, fusions = {}, {}, {}, []
    body = None
    for m in _LINE.finditer(text):
        if m.group("comp"):
            body = bodies[m.group("comp")] = []
            continue
        end = text.find("\n", m.end())
        if end < 0:
            end = len(text)
        at = text.find(_OP_NAME, m.end(), end)
        name = ""
        if at >= 0:
            at += len(_OP_NAME)
            name = text[at:text.find('"', at, end)]
        # thousands of instructions share a few hundred paths
        rec = by_path.get(name)
        if rec is None:
            rec = by_path[name] = classify(name)
        instr = m.group("instr")
        if CLONE_TOKEN in instr:
            rec = dict(rec, compiler_clone=True)
        ops[instr] = rec
        if body is not None and name:
            body.append(rec)
        at = text.find(_FUSION, m.end(), end)
        at = text.find(_CALLS, at, end) if at >= 0 else -1
        if at >= 0:
            fusions.append(
                (instr, _NAME.match(text, at + len(_CALLS)).group(1)))
    for instr, called in fusions:
        own = ops[instr]
        if any((r["scopes"][:1], r["pass"]) != (own["scopes"][:1],
                                                own["pass"])
               for r in bodies.get(called, ())):
            ops[instr] = dict(own, mixed=True)
    return ops


# --------------------------------------------------------------- the table

_M_EVICTED = telemetry.counter(
    "profiler.programs_evicted_total", "compiled programs the program "
    "table (paddle_tpu.profiler.note_program) dropped because it was "
    "full — the OLDEST goes first; program_ops() of a module name that "
    "lost a program answers None, never a partial table")


def _modules_of(compiled):
    """(module name, host-side modules or None, text or None) of a
    ``jax.stages.Compiled``, or of anything that prints itself."""
    try:
        modules = list(compiled.runtime_executable().hlo_modules())
        return modules[0].name, modules, None
    except (AttributeError, IndexError):
        text = compiled.as_text()
        head = re.match(r"HloModule\s+([\w.\-]+)", text)
        return (head.group(1) if head else "?"), None, text


class _Entry:
    """One filed program. Kept: its module name and its host-side modules
    (or text) until the first question, its parsed instructions from then
    on. Not kept: a weak reference, resolved on the first question if its
    owner still holds it."""

    __slots__ = ("key", "name", "modules", "text", "weak", "ops")

    def __init__(self, key, compiled, keep):
        self.key, self.ops = key, None
        self.name = self.modules = self.text = self.weak = None
        if keep:
            self.name, self.modules, self.text = _modules_of(compiled)
        else:
            self.weak = weakref.ref(compiled)

    def resolve(self) -> bool:
        """Whether the program can still be asked about; takes a weakly
        held program's modules if it is still there."""
        if self.weak is not None:
            compiled, self.weak = self.weak(), None
            if compiled is not None:
                self.name, self.modules, self.text = _modules_of(compiled)
        return self.name is not None

    def parsed(self) -> dict:
        if self.ops is None:
            text = self.text if self.text is not None else "\n".join(
                m.to_string() for m in self.modules)
            self.ops = parse_hlo_text(text)
            self.modules = self.text = None
        return self.ops


class ProgramTable:
    """The newest compiled programs of the process, under the key their
    owner filed them (``("segment", 16)``, ``"one_step"``), asked about by
    module name as a trace prints it (``jit_segment``). Bounded: room for
    ``floor`` programs, or twice what the largest owner said it holds; a
    program pushed out is counted, and its module name answers None until
    that program is filed again. ``register_s`` and ``parse_s`` are the
    seconds the table itself has cost at compile time and when asked."""

    def __init__(self, floor: int = 64):
        self._floor = int(floor)
        self._room = self._floor
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lost: dict = {}            # repr(key) -> module name
        self._lock = threading.Lock()
        self.register_s = 0.0
        self.parse_s = 0.0

    def note(self, key, compiled, owner_programs: int = 1,
             keep: bool = True) -> None:
        """File a ``jax.stages.Compiled`` (anything with
        ``runtime_executable().hlo_modules()``, else ``as_text()``) under
        ``key``; one filed again under the same key replaces the older.

        ``keep`` takes its host-side modules NOW, so that it can be asked
        about after its owner is gone: no executable and nothing on the
        device is held, but the chip's client rebuilds a module from a
        proto a call, 15-50 ms a serving program and 0.2 s a train step
        (my chip runs, PR 35). Without ``keep`` the table holds a weak
        reference, free at compile time, and takes the modules on the
        first question, which has to come while the owner lives."""
        t0 = time.perf_counter()
        entry = _Entry(key, compiled, keep)
        with self._lock:
            self._room = max(self._room, 2 * int(owner_programs))
            ident = repr(key)
            self._entries.pop(ident, None)
            self._lost.pop(ident, None)
            self._entries[ident] = entry
            while len(self._entries) > self._room:
                ident, old = self._entries.popitem(last=False)
                if old.resolve():
                    self._lost[ident] = old.name
                    _M_EVICTED.inc()
            self.register_s += time.perf_counter() - t0

    def _live(self) -> list:
        """The entries that can be asked about; one whose owner took it
        along is dropped (lock held)."""
        for ident in [i for i, e in self._entries.items()
                      if not e.resolve()]:
            del self._entries[ident]
        return list(self._entries.values())

    def programs(self) -> list:
        """``(module name, key)`` of what is held, oldest first."""
        with self._lock:
            return [(e.name, e.key) for e in self._live()]

    def ops(self, name: str | None = None):
        """``{instruction: record}`` of the programs filed under one module
        name; None for a name that was never filed, that went with its
        owner, or that lost a program to eviction. Where several programs
        share the name (the prefill programs, one an admission width) an
        instruction is kept only if every program that has it files it
        under the same scopes, pass and flags: the trace cannot say which
        of them ran. With no name, ``{module name: that}`` of every name
        the table knows."""
        if name is None:
            with self._lock:
                names = ({e.name for e in self._live()}
                         | set(self._lost.values()))
            return {n: self.ops(n) for n in sorted(names)}
        with self._lock:
            t0 = time.perf_counter()
            entries = [e for e in self._live() if e.name == name]
            if not entries or name in self._lost.values():
                return None
            tables = [e.parsed() for e in entries]
            self.parse_s += time.perf_counter() - t0
        if len(tables) == 1:
            return tables[0]
        merged, split = {}, set()
        for ops in tables:
            for instr, rec in ops.items():
                if _filed(merged.setdefault(instr, rec)) != _filed(rec):
                    split.add(instr)
        for instr in split:
            del merged[instr]
        return merged


def _filed(rec) -> tuple:
    return rec["scopes"], rec["pass"], rec["compiler_clone"], rec["mixed"]


_TABLE = ProgramTable()


def program_table() -> ProgramTable:
    """The process's one table."""
    return _TABLE


def note_program(key, compiled, owner_programs: int = 1,
                 keep: bool = True) -> None:
    _TABLE.note(key, compiled, owner_programs, keep)


def program_ops(name: str | None = None):
    return _TABLE.ops(name)


note_program.__doc__ = ProgramTable.note.__doc__
program_ops.__doc__ = ProgramTable.ops.__doc__


# ---------------------------------------------------------------- the join

def attribute_device_time(events, ops_of=None) -> dict:
    """Seconds by scope and by pass. ``events``: an iterable of (program,
    instruction, seconds) — a trace's device operations with their self
    time. ``ops_of(program)`` answers as ``program_ops`` does (the
    default). Per program, None where the table has nothing whole to say,
    else::

        {"seconds": all of the program's,
         "unmatched": of instructions the table does not know,
         "compiler_clone": of XLA's own clones, whatever their pass,
         "mixed": of fusions whose body holds another scope's or pass's
                  instructions (all of it filed under the fusion's own),
         "by_scope": {outermost scope or "": seconds},
         "by_pass": {"forward" | "backward" | "recompute" | "none": ...},
         "by_scope_pass": {"<scope>/<pass>": seconds},
         "unscoped_ops": {instruction: seconds}}

    ``by_scope`` and ``unmatched`` add up to ``seconds``, and so do
    ``by_pass`` and ``unmatched``."""
    ops_of = program_ops if ops_of is None else ops_of
    tables: dict = {}
    out: dict = {}
    for program, instruction, seconds in events:
        if program not in tables:
            ops = tables[program] = ops_of(program)
            out[program] = None if ops is None else {
                "seconds": 0.0, "unmatched": 0.0, "compiler_clone": 0.0,
                "mixed": 0.0, "by_scope": {}, "by_pass": {},
                "by_scope_pass": {},
                "unscoped_ops": {}}
        ops, acc = tables[program], out[program]
        if ops is None:
            continue
        acc["seconds"] += seconds
        rec = ops.get(instruction)
        if rec is None:
            acc["unmatched"] += seconds
            continue
        scope = rec["scopes"][0] if rec["scopes"] else UNSCOPED
        for table, k in ((acc["by_scope"], scope),
                         (acc["by_pass"], rec["pass"]),
                         (acc["by_scope_pass"], f"{scope}/{rec['pass']}")):
            table[k] = table.get(k, 0.0) + seconds
        if rec["compiler_clone"]:
            acc["compiler_clone"] += seconds
        if rec["mixed"]:
            acc["mixed"] += seconds
        if scope == UNSCOPED:
            acc["unscoped_ops"][instruction] = \
                acc["unscoped_ops"].get(instruction, 0.0) + seconds
    return out


# ------------------------------------------------- a trace's device events

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def self_seconds(modules, ops) -> list:
    """``modules``: ``(start, end, program)``; ``ops``: ``(start, duration,
    event name)``, one device's, any unit of time. Gives ``[program,
    instruction, self time]`` an operation: the program is the module
    event that encloses its start (``?`` outside any), the instruction
    the event's own name (``%fusion.12 = bf16[..] fusion(...)`` and
    ``fusion.12`` both give ``fusion.12``), and an operation that holds
    others (a ``while`` and its body) keeps only what they leave; one
    that a later operation outlasts (an asynchronous copy under the
    product that follows it) would be left with less than nothing and
    keeps nothing."""
    modules = sorted(modules)
    rows, stack, at = [], [], 0
    for start, dur, text in sorted(ops, key=lambda o: (o[0], -o[1])):
        while at + 1 < len(modules) and modules[at + 1][0] <= start:
            at += 1
        inside = modules and modules[at][0] <= start < modules[at][1]
        name = text.strip().lstrip("%").split(" ", 1)[0].split("=", 1)[0]
        row = [modules[at][2] if inside else "?", name, dur]
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            stack[-1][1][2] -= dur
        stack.append((start + dur, row))
        rows.append(row)
    for row in rows:
        row[2] = max(row[2], 0)
    return rows


def device_op_seconds(xplane_path: str) -> list:
    """``(program, instruction, self seconds)`` of every HLO operation on
    the TPU planes of a ``.xplane.pb`` (the "XLA Ops" line under the "XLA
    Modules" line; ``jit_segment(123)`` -> ``jit_segment``). Empty where
    the trace has no device plane (a CPU session)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line.events for line in plane.lines}
        modules = [(e.start_ns, e.start_ns + e.duration_ns,
                    re.sub(r"\(\d+\)$", "", e.name.strip()))
                   for e in lines.get("XLA Modules", ())]
        ops = [(e.start_ns, e.duration_ns, e.name)
               for e in lines.get("XLA Ops", ())]
        out.extend((p, n, s * 1e-9) for p, n, s in self_seconds(modules, ops))
    return out
