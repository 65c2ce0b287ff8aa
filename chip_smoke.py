#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, on one TPU,
at the full widths of the repo's target model (``LlamaConfig()``: hidden 4096,
32 heads x 128, intermediate 11008, vocab 32000, bf16, seeded random weights;
only depth is cut, to what 16 GB holds, and printed):

* **serve** — ``ContinuousBatchingEngine`` -> ``ServingFrontend``: warm-up,
  a few requests of mixed prompt lengths (bucketed, chunked and prefix-sharing
  admissions), all retiring ``ok`` with zero compiles after warm-up, pipelined
  and serial greedy streams identical, and the paged-cache path agreeing with
  the model's plain cache-less forward;
* **train** — ``jit.TrainStep`` + ``AdamW(multi_precision=True)``: a few
  steps on one seeded batch, loss finite and falling, flash attention in the
  compiled step.

``--chips 4`` runs instead ONLY the cross-chip paths and what they are
compared with: ``TPShardedEngine`` over ``serving_mesh(4)`` against the
single-chip engine, and one ``TrainStep`` over a dp2 x mp2 mesh against the
single-chip loss.

One process, no platform override, no fallback: without a TPU, or if any
phase raises or fails a check, the last line says ``"ok": false`` and the exit
code is not 0. A Pallas kernel running in the interpreter, the flash kernel
giving way to the XLA composition in the train step, or a native library that
cannot be built is such a failure. The script computes no rate or utilization;
every time it prints is host wall time. The phases are plain functions of a
config and sizes (``tests/test_chip_smoke.py`` runs them tiny on the CPU);
only ``main()`` holds the device gate and the real sizes.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
import traceback
import warnings

import jax
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import native, profiler
from paddle_tpu.core import telemetry
from paddle_tpu.jit import count_backend_compiles
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, llama_shard_fn
from paddle_tpu.models.frontend import ServingFrontend
from paddle_tpu.models.llama import PagedKVCache
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.models.tp_serving import TPShardedEngine, serving_mesh
from paddle_tpu.ops import nn_kernels, pallas

# What marks a Mosaic (compiled Pallas) kernel in a program's text. In
# interpret mode the kernel body is inlined as ordinary HLO and this is absent.
MOSAIC_CALL = "tpu_custom_call"

# bf16 keeps 8 significand bits (relative step 2**-8). Two evaluation orders
# of the same network — paged kernel vs masked composition, chunked vs whole
# prefill — round the residual stream differently at every layer, so their
# logits differ by a few such steps of the logit scale, growing slowly with
# depth; a cache fault (wrong page, position or length) moves logits by the
# order of their spread. 2**-4 of the largest |logit| separates the two with
# room on both sides (float32 runs land orders of magnitude below it).
LOGIT_TOL_REL = 2.0 ** -4
# Loss of a dp x mp step vs the single-chip step on the same weights and
# batch: row-parallel projections split contractions, so gradients and the
# steps taken differ at bf16 rounding level; a sharding fault changes what
# is learned and shows within a few steps as a different trajectory.
LOSS_TOL_REL = 2.0 ** -6


class SmokeFailure(AssertionError):
    """A phase ran but what came out is wrong."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def build_model(cfg, seed):
    """Seeded random weights, created in bf16 directly (no float32 copy)."""
    paddle.seed(seed)
    before = paddle.get_default_dtype()
    paddle.set_default_dtype("bfloat16")
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype(before)
    model.eval()
    return model


def param_bytes(model):
    return sum(p._value.size * p._value.dtype.itemsize
               for p in model.parameters())


def device_bytes_in_use():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use")


def mosaic_calls(compiled):
    return compiled.as_text().count(MOSAIC_CALL)


def device_time_split(prof, program):
    """How the program table splits a profiled session's device time of
    one program (``Profiler.summary()`` prints it), as shares of that
    program's seconds; None where the trace has no device plane (a CPU).
    ``unmatched`` is the share under instructions the table does not know:
    near 0 says the table is the traced executable's."""
    split = ((prof.summary() or {}).get(program) or {}).get("split")
    shutil.rmtree(prof.log_dir, ignore_errors=True)
    if not split:
        return None
    total = split["seconds"]
    return {"unmatched": round(split["unmatched"] / total, 4),
            **{kind: {k: round(v / total, 4) for k, v in split[kind].items()}
               for kind in ("by_pass", "by_scope")}}


def make_prompts(rng, vocab, prompt_lens, shared_prefix):
    """Random prompts of the given lengths; the last one repeats the first
    ``shared_prefix`` tokens of the longest earlier prompt, so it admits
    through the prefix cache (copy-on-write page + resume prefill)."""
    prompts = [rng.randint(0, vocab, (n,)).astype(np.int32)
               for n in prompt_lens]
    if shared_prefix:
        donor = max(prompts[:-1], key=len)
        check(len(donor) >= shared_prefix < len(prompts[-1]),
              "shared_prefix must fit the longest prompt and the last one")
        prompts[-1][:shared_prefix] = donor[:shared_prefix]
    return prompts


def serve_session(engine, prompts, max_new, segment):
    """One frontend session over ``engine``: submit everything, pump to the
    end, require every request ``ok`` and complete. Returns
    ``{rid: tokens}``."""
    fe = ServingFrontend(engine, segment=segment,
                         max_queue=max(64, len(prompts)))
    for i, (p, n) in enumerate(zip(prompts, max_new)):
        fe.submit(p, max_new_tokens=n, rid=i)
    results = fe.results(wait=True)
    bad = {rid: (r.status, r.reason) for rid, r in results.items()
           if r.status != "ok"}
    check(len(results) == len(prompts) and not bad,
          f"requests did not all retire ok: {bad or sorted(results)}")
    for i, n in enumerate(max_new):
        check(len(results[i].tokens) == n,
              f"request {i} emitted {len(results[i].tokens)} of {n} tokens")
    return {rid: np.asarray(r.tokens) for rid, r in results.items()}


def first_differences(a, b):
    """``{rid: index of the first differing token}`` over two stream sets."""
    out = {}
    for rid in sorted(a):
        if not np.array_equal(a[rid], b[rid]):
            n = min(len(a[rid]), len(b[rid]))
            out[rid] = next(
                (i for i in range(n) if a[rid][i] != b[rid][i]), n)
    return out


def check_streams_equal(a, b, what):
    for rid, first in first_differences(a, b).items():
        raise SmokeFailure(
            f"{what}: request {rid} differs from token {first}: "
            f"{a[rid][first:first + 4]} vs {b[rid][first:first + 4]}")


def logits_tolerance(ref, rel=LOGIT_TOL_REL):
    return rel * max(1.0, float(np.max(np.abs(ref))))


def check_cache_against_plain_forward(model, cfg, rng, plain, plain_len,
                                      page_size, n_prefill):
    """Prefill ``n_prefill`` seeded tokens into a paged cache, decode one
    more through it (the paged kernel), and compare both logits rows with
    the plain cache-less forward over the same tokens."""
    ids = rng.randint(0, cfg.vocab_size, (1, plain_len)).astype(np.int32)
    dtype = next(iter(model.parameters()))._value.dtype

    @paddle.jit.to_static
    def cached(prefix, tok):
        caches = [PagedKVCache(1, plain_len, cfg.num_key_value_heads,
                               cfg.head_dim, page_size=page_size, dtype=dtype)
                  for _ in range(cfg.num_hidden_layers)]
        logits_p, caches = model(prefix, caches=caches)
        logits_d, _ = model(tok, caches=caches)
        return logits_p[:, -1], logits_d[:, -1]

    with paddle.no_grad():
        ref = np.asarray(plain(paddle.to_tensor(ids))._value,
                         np.float32)[0]
        got_p, got_d = cached(
            paddle.to_tensor(ids[:, :n_prefill]),
            paddle.to_tensor(ids[:, n_prefill:n_prefill + 1]))
    got = np.stack([np.asarray(got_p._value, np.float32)[0],
                    np.asarray(got_d._value, np.float32)[0]])
    want = ref[n_prefill - 1:n_prefill + 1]
    check(np.all(np.isfinite(got)), "paged-cache logits are not finite")
    err = float(np.max(np.abs(got - want)))
    tol = logits_tolerance(want)
    check(err <= tol, f"paged-cache logits differ from the plain forward "
                      f"by {err:.4g} (tolerance {tol:.4g})")
    return {"max_abs_logit_diff": err, "tolerance": tol,
            "max_abs_logit": float(np.max(np.abs(want)))}


def check_streams_against_plain_forward(plain, plain_len, prompts, streams,
                                        rids):
    """Teacher-forced: every token the engine emitted for ``rids`` must be
    the plain forward's argmax at its position, or within the logit
    tolerance of it (a bf16 near-tie)."""
    exact = near = 0
    for rid in rids:
        seq = np.concatenate([prompts[rid], streams[rid]])
        check(len(seq) <= plain_len, f"request {rid} exceeds plain_len")
        ids = np.zeros((1, plain_len), np.int32)
        ids[0, :len(seq)] = seq
        with paddle.no_grad():
            logits = np.asarray(plain(paddle.to_tensor(ids))._value,
                                np.float32)[0]
        for i, tok in enumerate(streams[rid]):
            row = logits[len(prompts[rid]) - 1 + i]
            gap = float(row.max() - row[tok])
            tol = logits_tolerance(row)
            check(gap <= tol,
                  f"request {rid} token {i} ({tok}) is {gap:.4g} below the "
                  f"plain forward's argmax (tolerance {tol:.4g})")
            exact += gap == 0.0
            near += gap > 0.0
    return {"tokens_checked": exact + near, "argmax_exact": int(exact),
            "near_ties": int(near)}


def check_streams_agree(a, b, what, plain, prompts):
    """Greedy streams of two engines that evaluate the same bf16 model in
    different orders (four chips vs one): equal,
    except where bf16 leaves the choice open. A request whose streams
    differ must differ first at a near-tie — both candidates within the
    logit tolerance of the plain forward's best at that position, the
    context being common up to there — and from there on each stream is
    held to the plain forward on its own context. (Measured on the chip:
    the rounding points are XLA's to choose per program, so the two are
    NOT bit-identical in bf16 the way they are in float32.)"""
    longest = max(len(p) + len(a[rid]) for rid, p in enumerate(prompts))
    plain_len = -(-longest // 128) * 128   # one shape the flash kernel takes
    diverged = first_differences(a, b)
    for streams in (a, b):
        try:
            check_streams_against_plain_forward(
                plain, plain_len, prompts, streams, sorted(diverged))
        except SmokeFailure as e:
            raise SmokeFailure(f"{what}: streams differ at {diverged} and "
                               f"not at a near-tie: {e}") from e
    return {"identical_requests": len(a) - len(diverged),
            "requests": len(a), "first_difference_at_near_tie": diverged}


# ------------------------------------------------------------------ phases


def serve_phase(cfg, *, seed, max_slots, max_len, page_size, prompt_buckets,
                pool_pages, prompt_lens, shared_prefix, max_new, segment,
                plain_len):
    """Engine + frontend end to end; see the module docstring."""
    t0 = time.monotonic()
    rng = np.random.RandomState(seed)
    model = build_model(cfg, seed)
    engine_kw = dict(max_slots=max_slots, max_len=max_len,
                     page_size=page_size, prompt_buckets=prompt_buckets,
                     pool_pages=pool_pages)
    engine = ContinuousBatchingEngine(model, **engine_kw)
    pool_bytes = sum(k.size * k.dtype.itemsize
                     for k in engine._ks + engine._vs)
    report = {
        "layers": cfg.num_hidden_layers,
        "weight_bytes": param_bytes(model),
        "kv_pool_bytes": pool_bytes, "kv_pool_pages": pool_pages,
        "build_wall_s": round(time.monotonic() - t0, 2),
    }
    log(f"serve: depth {cfg.num_hidden_layers} layers, weights "
        f"{report['weight_bytes'] / 2**30:.2f} GiB, KV pool {pool_pages} "
        f"pages = {pool_bytes / 2**30:.2f} GiB")

    info = ServingFrontend(engine, segment=segment).warmup()
    report["warmup_programs"] = info["programs"]
    report["warmup_wall_s"] = round(info["seconds"], 2)
    programs = engine.compiled_programs()
    kernels = {}
    for key, exe in programs.items():
        kernels.setdefault(key[0], set()).add(mosaic_calls(exe))
    report["mosaic_calls_by_program"] = {
        k: sorted(v) for k, v in sorted(kernels.items())}
    report["mosaic_in_segment"] = (
        mosaic_calls(programs[("segment", segment)]) > 0)
    log(f"serve: warm-up compiled {info['programs']} programs in "
        f"{info['seconds']:.1f} s wall; Mosaic kernel calls per program "
        f"{report['mosaic_calls_by_program']}")

    prompts = make_prompts(rng, cfg.vocab_size, prompt_lens, shared_prefix)
    chunk_w = max(prompt_buckets)
    check(any(len(p) <= chunk_w for p in prompts)
          and any(len(p) > chunk_w for p in prompts),
          "prompt_lens must mix bucketed and chunked admissions")
    serving_compiles = telemetry.counter("xla.compiles_total")
    before = serving_compiles.value(phase="serving")
    t1 = time.monotonic()
    with count_backend_compiles() as compiles, profiler.Profiler() as prof:
        piped = serve_session(engine, prompts, max_new, segment)
        check(engine.stats()["pipelined"], "first session was not pipelined")
        kv = engine.kv_stats()
    # the serial scheduler is the reference, on an engine of its own. The
    # two pools do not fit the chip together, so the first goes first; the
    # second compiles what it runs (from the persistent cache where the
    # first one's programs are in it).
    del engine
    gc.collect()
    serial_engine = ContinuousBatchingEngine(model, pipeline=False,
                                             **engine_kw)
    serial = serve_session(serial_engine, prompts, max_new, segment)
    check(not serial_engine.stats()["pipelined"],
          "second session was not serial")
    del serial_engine
    report["requests_wall_s"] = round(time.monotonic() - t1, 2)
    report["requests"] = len(prompts)
    report["tokens_per_session"] = int(sum(max_new))
    report["prefix_tokens_saved"] = kv["prefix_tokens_saved"]
    report["post_warmup_compiles_serving"] = (
        serving_compiles.value(phase="serving") - before)
    report["post_warmup_compiles_any"] = len(compiles)
    check(report["post_warmup_compiles_serving"] == 0 and not compiles,
          f"compiled after warm-up: {report['post_warmup_compiles_serving']} "
          f"by the engine's count, {len(compiles)} backend compiles in all")
    if shared_prefix:
        check(kv["prefix_tokens_saved"] > 0,
              "the prefix-sharing request did not hit the prefix cache")
    check_streams_equal(piped, serial, "pipelined vs serial")
    log(f"serve: {len(prompts)} requests x 2 sessions (pipelined, serial) "
        f"all ok, streams identical, 0 compiles after warm-up in the "
        f"pipelined one, {kv['prefix_tokens_saved']} prompt tokens served "
        f"from the prefix cache")
    # both engines are gone; what the first one's warm-up filed for its
    # decode program is still in the profiler's program table, and splits
    # the profiled session's device time by scope
    seg_ops = profiler.program_ops("jit_segment") or {}
    scopes = {r["scopes"][0] for r in seg_ops.values() if r["scopes"]}
    check({"attn", "mlp", "lm_head", "sample"} <= scopes,
          f"program_ops('jit_segment') after both engines were deleted: "
          f"{len(seg_ops)} instructions, scopes {sorted(scopes)}")
    report["segment_time_split"] = device_time_split(prof, "jit_segment")
    log(f"serve: program table holds jit_segment's {len(seg_ops)} "
        f"instructions after both engines were deleted; the profiled "
        f"session's device time: {report['segment_time_split']}")

    plain = paddle.jit.to_static(model)
    report["cache_vs_plain"] = check_cache_against_plain_forward(
        model, cfg, rng, plain, plain_len, page_size,
        n_prefill=min(plain_len - 1, 2 * page_size))
    fits = [i for i, p in enumerate(prompts)
            if len(p) + max_new[i] <= plain_len]
    short = min(fits, key=lambda i: len(prompts[i]))
    long_ = max(fits, key=lambda i: len(prompts[i]))
    check(len(prompts[long_]) > chunk_w,
          "plain_len leaves no chunked request to compare")
    report["streams_vs_plain"] = check_streams_against_plain_forward(
        plain, plain_len, prompts, piped, sorted({short, long_, fits[-1]}))
    log(f"serve: paged cache vs plain forward {report['cache_vs_plain']}; "
        f"engine streams vs plain forward {report['streams_vs_plain']}")
    report["wall_s"] = round(time.monotonic() - t0, 2)
    return report


def adamw_train_step(model, lr):
    """``TrainStep`` over AdamW with fp32 masters and bf16 moments. The
    model is called as ``model(ids, None, None, labels)``, which returns
    the fused lm-head + cross-entropy loss itself."""
    opt = paddle.optimizer.AdamW(
        learning_rate=lr, parameters=model.parameters(),
        multi_precision=True, acc_dtype="bfloat16")
    return paddle.jit.TrainStep(model, lambda loss: loss, opt)


def train_phase(cfg, *, seed, batch, seq, steps, lr):
    """A few compiled train steps on one repeated seeded batch."""
    t0 = time.monotonic()
    rng = np.random.RandomState(seed)
    model = build_model(cfg, seed)
    model.train()
    step = adamw_train_step(model, lr)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    nn_kernels._flash_fallback_warned.clear()
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*falling back to the XLA sdpa composition.*")
        compiled = step.lower(ids, None, None, ids).compile()
        mem = compiled.memory_analysis()
        calls = mosaic_calls(compiled)
        losses = [float(step(ids, None, None, ids))
                  for _ in range(steps - 1)]
        # the last step under the profiler: its summary splits the step's
        # device time by pass and by scope where the trace has a device
        with profiler.Profiler() as prof:
            losses.append(float(step(ids, None, None, ids)))
    check(all(np.isfinite(losses)), f"loss is not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    split = device_time_split(prof, "jit_one_step")
    report = {
        "layers": cfg.num_hidden_layers, "batch": batch, "seq": seq,
        "weight_bytes": param_bytes(model),
        "step_argument_bytes": mem.argument_size_in_bytes,
        "step_temp_bytes": mem.temp_size_in_bytes,
        "mosaic_in_step": calls > 0, "mosaic_calls_in_step": calls,
        "step_time_split": split,
        "losses": [round(x, 4) for x in losses],
        "wall_s": round(time.monotonic() - t0, 2),
    }
    log(f"train: depth {cfg.num_hidden_layers} layers, batch {batch} x seq "
        f"{seq}, bf16 weights {report['weight_bytes'] / 2**30:.2f} GiB + "
        f"fp32 masters + bf16 moments: step operands "
        f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
        f"{mem.temp_size_in_bytes / 2**30:.2f} GiB; {calls} Mosaic calls in "
        f"the step; losses {report['losses']}")
    return report


def shards_report(arrays, what, n_devices):
    """Every array must have addressable shards on ``n_devices`` distinct
    devices; returns the bytes each device holds of them."""
    per_device = {}
    for a in arrays:
        devs = {s.device.id for s in a.addressable_shards}
        check(len(devs) == n_devices,
              f"{what}: an array lives on devices {sorted(devs)}, "
              f"not on {n_devices}")
        for s in a.addressable_shards:
            per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                       + s.data.size * s.data.dtype.itemsize)
    return per_device


def check_every_device_holds_memory(n_devices, floor_bytes):
    used = {}
    for dev in jax.devices()[:n_devices]:
        stats = dev.memory_stats()
        if stats is None:      # the CPU backend keeps no such statistics
            return None
        used[dev.id] = stats["bytes_in_use"]
    check(all(v >= floor_bytes for v in used.values()),
          f"a device holds almost nothing: bytes_in_use {used}, expected "
          f"at least {floor_bytes} on each")
    return used


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def collectives_in(text):
    return sorted(c for c in COLLECTIVES if c in text)


def multichip_phase(cfg, *, seed, chips, max_slots, max_len, page_size,
                    prompt_buckets, prompt_lens, max_new, segment,
                    batch, seq, steps, lr):
    """The cross-chip paths and what they are compared with, nothing else."""
    t0 = time.monotonic()
    rng = np.random.RandomState(seed)
    check(len(jax.devices()) >= chips,
          f"need {chips} devices, JAX sees {len(jax.devices())}")
    model = build_model(cfg, seed)
    prompts = make_prompts(rng, cfg.vocab_size, prompt_lens, 0)
    kw = dict(max_slots=max_slots, max_len=max_len, page_size=page_size,
              prompt_buckets=prompt_buckets)

    single = ContinuousBatchingEngine(model, **kw)
    want = serve_session(single, prompts, max_new, segment)
    del single
    gc.collect()
    plain = paddle.jit.to_static(model)

    tp = TPShardedEngine(model, mesh=serving_mesh(chips), **kw)
    ServingFrontend(tp, segment=segment).warmup()
    got = serve_session(tp, prompts, max_new, segment)
    agree = check_streams_agree(
        got, want, f"TP over {chips} chips vs one chip", plain, prompts)
    del plain
    seg_text = tp.compiled_programs()[("segment", segment)].as_text()
    serve_coll = collectives_in(seg_text)
    check(serve_coll, "no collective in the TP segment program")
    sharded = [v for v in tp._param_snapshot().values()
               if not v.sharding.is_fully_replicated]
    check(sharded, "the TP plan sharded no parameter")
    report = {
        "chips": chips, "layers": cfg.num_hidden_layers,
        "serve_tp_vs_single": agree, "serve_collectives": serve_coll,
        "serve_mosaic_in_segment": MOSAIC_CALL in seg_text,
        "serve_param_shard_bytes": shards_report(
            sharded, "TP parameters", chips),
        "serve_kv_shard_bytes": shards_report(
            tp._ks + tp._vs, "TP KV pools", chips),
    }
    floor = min(report["serve_kv_shard_bytes"].values())
    report["serve_bytes_in_use"] = check_every_device_holds_memory(
        chips, floor)
    log(f"multichip serve: TP={chips} vs single-chip streams {agree}; "
        f"collectives {serve_coll}; parameter "
        f"shard bytes per device {report['serve_param_shard_bytes']}; KV "
        f"pool shard bytes per device {report['serve_kv_shard_bytes']}; "
        f"bytes_in_use per device {report['serve_bytes_in_use']}")
    del tp, sharded, model
    gc.collect()

    # a few train steps: one chip, then the same seeded model over dp x mp
    ids_np = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    def few_steps(shard):
        m = build_model(cfg, seed)
        m.train()
        ids = paddle.to_tensor(ids_np)
        if shard:
            mesh = dist.ProcessMesh(
                np.arange(chips).reshape(chips // 2, 2), ["dp", "mp"])
            dist.set_mesh(mesh)
            dist.shard_layer(m, mesh, llama_shard_fn(mesh))
            ids = dist.shard_tensor(ids, mesh,
                                    [dist.Shard(0), dist.Replicate()])
        step = adamw_train_step(m, lr)
        out = {"losses": [float(step(ids, None, None, ids))
                          for _ in range(steps)]}
        if shard:
            text = step.lower(ids, None, None, ids).compile().as_text()
            out["collectives"] = collectives_in(text)
            out["mosaic"] = MOSAIC_CALL in text
            mp_sharded = [p._value for p in m.parameters()
                          if not p._value.sharding.is_fully_replicated]
            check(mp_sharded, "llama_shard_fn sharded no parameter")
            out["param_shard_bytes"] = shards_report(
                mp_sharded, "dp x mp parameters", chips)
            out["bytes_in_use"] = check_every_device_holds_memory(
                chips, min(out["param_shard_bytes"].values()))
        return out

    try:
        one_chip = few_steps(shard=False)
        gc.collect()
        meshed = few_steps(shard=True)
    finally:
        dist.set_mesh(None)
    check(np.all(np.isfinite(meshed["losses"])),
          f"sharded loss is not finite: {meshed['losses']}")
    check(one_chip["losses"][-1] < one_chip["losses"][0],
          f"single-chip loss did not fall: {one_chip['losses']}")
    for i, (a, b) in enumerate(zip(meshed["losses"], one_chip["losses"])):
        check(abs(a - b) <= LOSS_TOL_REL * abs(b),
              f"dp x mp loss at step {i} is {a}, single-chip {b} "
              f"(relative tolerance {LOSS_TOL_REL})")
    check(meshed["collectives"], "no collective in the dp x mp step")
    report.update(
        train_losses_single=[round(x, 4) for x in one_chip["losses"]],
        train_losses_mesh=[round(x, 4) for x in meshed["losses"]],
        train_collectives=meshed["collectives"],
        train_mosaic_in_step=meshed["mosaic"],
        train_param_shard_bytes=meshed["param_shard_bytes"],
        train_bytes_in_use=meshed["bytes_in_use"],
        wall_s=round(time.monotonic() - t0, 2))
    log(f"multichip train: dp{chips // 2} x mp2 losses "
        f"{report['train_losses_mesh']} vs single-chip "
        f"{report['train_losses_single']}; collectives "
        f"{meshed['collectives']}; parameter shard bytes per device "
        f"{meshed['param_shard_bytes']}; bytes_in_use per device "
        f"{meshed['bytes_in_use']}")
    return report


# ------------------------------------------------------ real sizes + gate

SEED = 0

# LlamaConfig() IS the repo's target model (BASELINE.json: LLaMA-7B); no
# width is touched. Depth is what 16 GB holds. Serving: 16 of 32 layers,
# 6.5 GiB of bf16 weights next to a 6 GiB KV pool and the segment program's
# 1.5 GiB of temporaries. Training: 5 layers — bf16 weights + fp32 masters
# + bf16 moments are 10 bytes a parameter, 11.9 GiB of step operands; 6
# layers ran too, at 15.0 of the chip's 15.75 GiB, which leaves a later PR
# no room.
SERVE = dict(
    config=dict(num_hidden_layers=16),
    max_slots=4, max_len=4096, page_size=128, prompt_buckets=(128,),
    pool_pages=192,
    prompt_lens=(9, 300, 77, 128, 40, 450, 100, 260), shared_prefix=200,
    max_new=(32, 48, 64, 40, 56, 48, 64, 32), segment=16, plain_len=512)
TRAIN = dict(config=dict(num_hidden_layers=5, use_recompute=True),
             batch=2, seq=1024, steps=5, lr=3e-4)
MULTICHIP = dict(
    config=dict(num_hidden_layers=4, use_recompute=True), chips=4,
    max_slots=4, max_len=1024, page_size=128, prompt_buckets=(128,),
    prompt_lens=(9, 120, 300, 64), max_new=(32,) * 4, segment=16,
    # a small step keeps the loss trajectory smooth, so the two runs are
    # compared where bf16 noise is far below the tolerance
    batch=4, seq=1024, steps=3, lr=2e-5)


def watch_compile_cache():
    """Count persistent-cache hits and misses (a repeat run in the same
    cache directory shows hits)."""
    from jax._src import monitoring

    seen = {"hits": 0, "misses": 0}

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            seen["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            seen["misses"] += 1

    monitoring.register_event_listener(on_event)
    return seen


def run(chips, device):
    check(not pallas.interpret(), "Pallas kernels would run in the "
                                  "interpreter on this backend")
    for name in ("tcp_store", "token_reader"):
        check(native.load_library(name) is not None,
              f"native library {name} could not be built (no toolchain?)")
    log("native libraries tcp_store, token_reader: built from source and "
        "loaded")
    cache_dir = paddle.jit.enable_compilation_cache()
    cache = watch_compile_cache()
    log(f"persistent compile cache at {cache_dir}")
    summary = {"device": device, "compile_cache_dir": cache_dir}

    def phase(name, fn, spec, require):
        spec = dict(spec)
        cfg = LlamaConfig(**spec.pop("config"))
        with count_backend_compiles() as compiles:
            report = fn(cfg, seed=SEED, **spec)
        report["backend_compiles"] = len(compiles)
        for key in require:
            check(report[key], f"{name}: no Mosaic kernel in the compiled "
                               f"program ({key} is false)")
        gc.collect()
        log(f"{name}: {len(compiles)} backend compiles, "
            f"{report['wall_s']} s wall, device bytes in use after "
            f"release {device_bytes_in_use()}")
        summary[name] = report

    if chips == 4:
        phase("multichip", multichip_phase, MULTICHIP,
              ("serve_mosaic_in_segment", "train_mosaic_in_step"))
    else:
        phase("serve", serve_phase, SERVE, ("mosaic_in_segment",))
        phase("train", train_phase, TRAIN, ("mosaic_in_step",))
        # the program table is the traced executables' own: the profiled
        # sessions' device time is split by scope and by pass with next to
        # nothing under instructions the table does not know
        for name, key, kind, want in (
                ("serve", "segment_time_split", "by_scope",
                 ("attn", "mlp", "lm_head")),
                ("train", "step_time_split", "by_pass",
                 ("forward", "backward", "recompute", "none"))):
            split = summary[name][key] or {"unmatched": 1.0, kind: {}}
            check(split["unmatched"] < 0.05
                  and all(split[kind].get(k, 0) > 0 for k in want),
                  f"{name}: device time is not attributed {kind} "
                  f"(Profiler.summary() over the program table): {split}")
    summary["compile_cache"] = cache
    log(f"persistent compile cache: {cache['hits']} hits, "
        f"{cache['misses']} misses")
    summary["claim"] = None
    print(json.dumps(summary), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths and their "
                         "single-chip comparison (default 1)")
    args = ap.parse_args(argv)
    device = None
    try:
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        check(device["platform"] == "tpu",
              f"no TPU: JAX's default platform is {device['platform']!r}")
        check(len(devs) >= args.chips,
              f"--chips {args.chips} but JAX sees {len(devs)} devices")
        log(f"device: {device}")
        run(args.chips, device)
    except BaseException as e:   # report, then fail — never carry on
        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "reason": f"{type(e).__name__}: {e}"[:2000],
                          "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
