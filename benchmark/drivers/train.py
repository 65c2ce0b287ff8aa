"""Training driver: ``TrainStep.__call__`` over the repo's ``DataLoader``.

Set-up builds ONE object, the compiled step with its state, and drives it from
the seed through its first steps (the traffic file's ``warm_steps``) with the
window's own call and feed, on rows that all differ; the same object then runs
the window: whole steps until ``--seconds`` have passed, ending on
``block_until_ready`` of the last. What the first steps produced (each loss,
the first gradient's norm per leaf worked out from the optimizer's first
moment, the parameters' change per leaf) is compared with the plain reference
once the window has closed and the program's state is freed.
"""
from __future__ import annotations

import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, costs, program, tracing
from benchmark import weights as W


@jax.jit
def _norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _change_norms(now, start):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        now[k].astype(jnp.float32) - start[k].astype(jnp.float32))))
        for k in now}


def first_grad_norms(step, beta1: float) -> dict:
    """After one step from zero moments the first moment is (1 - beta1) g:
    the gradient as the optimizer got it, per leaf."""
    m1 = {k.split("@", 1)[1]: v for k, v in step._accs.items()
          if k.startswith("moment1@")}
    return {k: float(v) / (1.0 - beta1) for k, v in _norms(m1).items()}


def change_norms(step, model, config, seed) -> dict:
    """Norm per leaf of (float32 master now - the seeded start), the start
    made again from the seed (no copy of it is kept through the steps)."""
    # a leaf without a master (a float32 leaf) is its own master
    now = {k: step._masters.get(k, p._value)
           for k, p in model.named_parameters()}
    start = W.make_weights(config["model"], seed,
                           jnp.dtype(config["deployment"]["dtype"]))
    return {k: float(v) for k, v in _change_norms(now, start).items()}


def make_loader(traffic, seed, vocab):
    from paddle_tpu.io import DataLoader, IterableDataset

    gen = importlib.import_module(traffic["generator"])

    class Packed(IterableDataset):
        def __iter__(self):
            return gen.sequences(traffic, seed, vocab)

    return iter(DataLoader(Packed(), batch_size=int(traffic["batch"])))


def run(ctx: dict) -> dict:
    import paddle_tpu as paddle

    log, config, traffic = ctx["log"], ctx["config"], ctx["traffic"]
    m, seconds, seed = config["model"], ctx["seconds"], ctx["seed"]
    kind = ctx["device"]["kind"]
    tcfg = config["deployment"]["train"]
    log(f"compile cache at {paddle.jit.enable_compilation_cache()} ({kind})")

    t0 = time.monotonic()
    model = program.build_model(config, seed, recompute=tcfg["recompute"])
    step = program.build_train_step(model, config)
    loader = make_loader(traffic, seed, m["vocab_size"])
    batch, seq = int(traffic["batch"]), int(traffic["seq_len"])

    def one_step():
        ids = program.feed(next(loader))
        return step(ids, None, None, ids)

    # ---- the first steps: the window's own object, call and feed
    observed = {"losses": []}
    for i in range(int(traffic["warm_steps"])):
        observed["losses"].append(float(one_step()))
        if i == 0:
            observed["grad_norms"] = first_grad_norms(step, tcfg["beta1"])
    observed["change_norms"] = change_norms(step, model, config, seed)
    log(f"built, compiled and ran {traffic['warm_steps']} steps in "
        f"{time.monotonic() - t0:.1f} s; losses {observed['losses']} on {kind}")

    # ---- the window: whole steps, one in flight ahead of the host
    tracer = tracing.Tracer(ctx["workdir"], ctx["trace"])
    trace_s = min(float(traffic.get("trace_s", 3.0)), seconds)
    losses, done_at = [], []
    w0 = time.monotonic()
    prev = None
    while True:
        if time.monotonic() >= w0 + seconds - trace_s:
            tracer.start_once()
        with tracer.span("bench.step"):
            cur = one_step()
        if prev is not None:
            with tracer.span("bench.wait_step"):
                losses.append(float(prev))
            done_at.append(time.monotonic())
        prev = cur
        if time.monotonic() - w0 >= seconds:
            break
    losses.append(float(prev))
    w1 = time.monotonic()
    done_at.append(w1)
    tracer.stop()
    steps = len(losses)
    tokens = steps * batch * seq
    log(f"window {w1 - w0:.3f} s, {steps} whole steps, {tokens} tokens, "
        f"last loss {losses[-1]:.4f} on {kind}")

    from benchmark.harness import memory_peak_bytes
    peak = memory_peak_bytes(ctx["chips"])
    bad = sum(1 for x in losses if not np.isfinite(x))
    bag = {
        "kind": "train", "model": m, "config": config, "chips": ctx["chips"],
        "device_kind": kind, "window": (w0, w1), "steps": steps,
        "batch": batch, "seq": seq, "tokens": tokens, "done_at": done_at,
        "end_to_end": {"setup_s": w0 - ctx["t_process"],
                       "train_tok_s": tokens / (w1 - w0)},
        "attempted": steps, "failed": bad, "memory_peak_bytes": peak,
    }
    bag["trace"] = tracer.reduce(bag)

    # ---- free the program, then the plain reference follows the first steps
    del step, model, loader, cur, prev
    gc.collect()
    t_ref = time.monotonic()
    checks, detail = compare.training_checks(
        config, traffic, seed, observed, ctx["limits"],
        control_mm=ctx.get("control"))
    checks.append(("nonfinite_losses", float(bad), 0.0))
    log(f"reference took {time.monotonic() - t_ref:.1f} s: {detail} on {kind}")
    bag["checks"] = checks
    bag["notes"] = {"reference": detail, "device": kind,
                    "step_flops": costs.train_flops_tokens(m, batch, seq)}
    return bag

