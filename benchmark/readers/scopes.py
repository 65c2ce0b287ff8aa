"""Per-layer metrics that split a compiled program's device time by the
``jax.named_scope`` and by the pass its operations came from.

The device trace names an operation by its HLO instruction and its module
(``jit_segment:iota_reduce_fusion.2``). Which scope and which pass that
instruction came from is kept by the program itself, for every program it
compiles (``paddle_tpu.profiler.note_program`` / ``program_ops``), and the
join is the program's too (``paddle_tpu.profiler.attribute_device_time``):
this reader hands it the traced operations' (program, instruction, self
seconds) and turns what comes back into a share. The patterns (which
program, which outermost scopes, which pass) are the metric files'.

Every reader returns None where there is nothing whole to read: no trace, a
bag of another kind, a program that keeps no such table (an older one), no
table for the traced program, a table that lost a program to eviction, or
more than ``max_unmatched_pct`` of the program's device seconds under
instructions the table does not know: a table that is not the traced
executable's must not produce a number.
"""
from __future__ import annotations

import re

KINDS = ("serve", "train")
TOP_UNSCOPED = 12


def _split(bag, program):
    """The join over the traced operations of the programs whose name
    matches, summed over those programs, read once per bag and pattern;
    None where any of them has no whole table."""
    if bag.get("kind") not in KINDS or not bag.get("trace_events"):
        return None
    cache = bag.setdefault("scope_split", {})
    if program in cache:
        return cache[program]
    cache[program] = None
    try:
        from paddle_tpu.profiler import attribute_device_time
    except ImportError:          # a program from before the table
        return None
    rx = re.compile(program)
    # an asynchronous operation that a later one outlasts reads a negative
    # self time in the trace's arithmetic: it counts as nothing
    events = [(e["program"], e["op"], max(e["self"], 0.0))
              for e in bag["trace_events"]
              if e["kind"] == "op" and rx.search(e["program"])]
    by_program = attribute_device_time(events)
    if not by_program or any(v is None for v in by_program.values()):
        return None
    total: dict = {}
    for split in by_program.values():
        for key, value in split.items():
            if isinstance(value, dict):
                table = total.setdefault(key, {})
                for k, v in value.items():
                    table[k] = table.get(k, 0.0) + v
            else:
                total[key] = total.get(key, 0.0) + value
    if not total["seconds"]:
        return None
    cache[program] = total
    _note(bag, program, total)
    return total


def _note(bag, program, total):
    """The whole split rides out in the result line's ``notes``: every
    scope and pass as a share, and the largest operations with no scope."""
    secs = total["seconds"]

    def pct(table):
        return {k: round(100.0 * v / secs, 3) for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])}

    top = sorted(total["unscoped_ops"].items(),
                 key=lambda kv: -kv[1])[:TOP_UNSCOPED]
    bag.setdefault("notes", {}).setdefault("device_time_split", {})[
        program] = {
        "seconds": secs,
        "unmatched_pct": round(100.0 * total["unmatched"] / secs, 3),
        "compiler_clone_pct": round(
            100.0 * total["compiler_clone"] / secs, 3),
        "mixed_fusions_pct": round(
            100.0 * total.get("mixed", 0.0) / secs, 3),
        "by_scope_pct": pct(total["by_scope"]),
        "by_pass_pct": pct(total["by_pass"]),
        "by_scope_pass_pct": pct(total["by_scope_pass"]),
        "unscoped_top_pct": pct(dict(top))}


def share_pct(bag, program, scope=None, of_pass=None, clone=False,
              max_unmatched_pct=5.0):
    """Self seconds of the traced operations of ``program`` whose
    OUTERMOST scope matches ``scope`` (the empty string is "no scope at
    all": ``^$``), or whose pass matches ``of_pass``, or that XLA cloned
    (``clone``), over the self seconds of all of that program's
    operations. Exactly one of the three selects."""
    total = _split(bag, program)
    if total is None:
        return None
    secs = total["seconds"]
    if 100.0 * total["unmatched"] / secs > max_unmatched_pct:
        return None
    if clone:
        part = total["compiler_clone"]
    else:
        table = total["by_scope"] if of_pass is None else total["by_pass"]
        rx = re.compile(scope if of_pass is None else of_pass)
        part = sum(v for k, v in table.items() if rx.search(k))
    return 100.0 * part / secs
