"""``drivers/serve.py``'s twin for the ``nemotron_h`` hybrid model, after
``drivers/serve_retention.py``: the serving driver is model-agnostic but for
the two modules it names at its top (``program``, ``compare``), and this PR
may edit no benchmark file that is there. So this driver runs ``serve.run``
itself, unchanged, with those two names pointing at their twins for the
length of the call, and adds to the bag what the new readers need
(``benchmark/readers/nemotron_h.py``). A traffic file names it under
``driver``.
"""
from __future__ import annotations

import contextlib

from benchmark import compare_nemotron_h, program_nemotron_h
from benchmark.drivers import serve


@contextlib.contextmanager
def twins():
    """``serve``'s ``program`` and ``compare`` are the twins inside."""
    before = serve.program, serve.compare
    serve.program, serve.compare = program_nemotron_h, compare_nemotron_h
    try:
        yield
    finally:
        serve.program, serve.compare = before


def run(ctx: dict) -> dict:
    with twins():
        bag = serve.run(ctx)
    bag["model"] = program_nemotron_h.model_section(ctx["config"])
    facts = program_nemotron_h.ENGINE_FACTS
    bag["state_bytes_per_slot"] = facts.get("state_bytes_per_slot")
    bag["kv_bytes_per_token"] = facts.get("kv_bytes_per_token")
    return bag
