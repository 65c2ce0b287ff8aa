"""``drivers/serve.py``'s twin for the power-retention model, after
``drivers/serve_moe_mla.py``: the serving driver is model-agnostic but for the
two modules it names at its top (``program``, ``compare``), and this PR may
edit no benchmark file that is there. So this driver runs ``serve.run``
itself, unchanged, with those two names pointing at their twins for the
length of the call, and adds to the bag what the new readers need
(``benchmark/readers/retention.py``). A traffic file names it under
``driver``.
"""
from __future__ import annotations

import contextlib

from benchmark import compare_retention, program_retention
from benchmark.drivers import serve


@contextlib.contextmanager
def twins():
    """``serve``'s ``program`` and ``compare`` are the twins inside."""
    before = serve.program, serve.compare
    serve.program, serve.compare = program_retention, compare_retention
    try:
        yield
    finally:
        serve.program, serve.compare = before


def run(ctx: dict) -> dict:
    with twins():
        bag = serve.run(ctx)
    bag["model"] = program_retention.model_section(ctx["config"])
    bag["state_bytes_per_slot"] = \
        program_retention.ENGINE_FACTS.get("state_bytes_per_slot")
    return bag
