"""``drivers/serve.py``'s twin for the sparse-expert / latent-attention
model. The serving driver is model-agnostic but for the two modules it names
at its top (``program``: ``LlamaForCausalLM``; ``compare``: ``llama_plain``),
and this PR may edit no benchmark file that is there. So this driver runs
``serve.run`` itself, unchanged, with those two names pointing at their twins
for the length of the call, and adds to the bag what the new readers need
(``benchmark/readers/moe_mla.py``). A traffic file names it under ``driver``.
ROADMAP (Metrics and harness) asks a ``benchmark`` issue to let a
configuration name its program, weights, reference and cost modules, and to
fold this twin back.
"""
from __future__ import annotations

import contextlib

from benchmark import compare_moe_mla, program_moe_mla
from benchmark.drivers import serve


@contextlib.contextmanager
def twins():
    """``serve``'s ``program`` and ``compare`` are the twins inside."""
    before = serve.program, serve.compare
    serve.program, serve.compare = program_moe_mla, compare_moe_mla
    try:
        yield
    finally:
        serve.program, serve.compare = before


def run(ctx: dict) -> dict:
    with twins():
        bag = serve.run(ctx)
    bag["model"] = program_moe_mla.model_section(ctx["config"])
    bag["kv_bytes_per_token"] = \
        program_moe_mla.ENGINE_FACTS["kv_bytes_per_token"]
    return bag
