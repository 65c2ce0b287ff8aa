#!/usr/bin/env python
"""bench.py — end-of-round benchmark run by the driver on real TPU hardware.

Sections (every end-to-end number carries an IN-RUN calibration so a slow
run of the whole machine is distinguishable from a real regression). The
script predates the chip tool and ``chip_smoke.py`` and has not been run
since PR 1; the benchmark PR (ROADMAP S0) replaces it:
  (a) 8192^3 bf16 matmul — the run's compute calibration (TFLOP/s)
  (b) LLaMA 438M train step (fused lm-head+CE, TrainStep multi-step)
  (b2) LLaMA ~1.3B train step: recompute + fp32 master + bf16 Adam moments
       (the largest-fits-16GB config; BASELINE configs 4/5 proxy)
  (c) resnet50 (BASELINE config 1 as written) + resnet18 (round continuity)
  (c2) BERT-base fused-attention train step (BASELINE config 2)
  (d) Pallas paged decode attention kernel + its streaming-floor calibration
  (e) whole-model compiled decode (generate(), paged caches)
      + (e2) continuous batching + (e3) replica-fleet router overhead gate
      + (e4) durable-router write-ahead journal overhead gate
      + (e5) telemetry overhead gate (tracing + metrics registry, default-on)
      + (e6) perfwatch overhead gate (phase attribution, KV/memory/compile
        watchdogs, SLO burn-rate monitor, default-on)
      + (e7) overload control: flash-crowd drill gating autoscaler
        reaction/overshoot/overhead + brownout goodput floor/recovery
  (f) per-op microbench: adaptive iters (no 0.0us clamp readings), compared
      against OPBENCH_BASELINE.json, then the baseline is RE-RECORDED with
      this run's numbers (reference: tools/ci_op_benchmark.sh relative gate)
  (g) end-to-end regression gate: per-TFLOP-calibrated ratios vs
      BENCH_BASELINE.json (auto-re-recorded per round)

Single process (the chip is single-tenant), tolerant of minutes-long first
device contact, progress on stderr, and EXACTLY ONE JSON line on stdout:
  {"metric": "llama_train_mfu", "value": <pct>, "unit": "%", "vs_baseline": R}
vs_baseline = MFU / 0.50 — the fraction of the BASELINE.md north-star target
(>=50% MFU on the auto-parallel LLaMA configs); the reference publishes no
absolute in-tree numbers to compare against (BASELINE.json.published = {}).

Local CPU smoke test: python bench.py --cpu
"""
from __future__ import annotations

import json
import os
import sys
import time

t0 = time.time()


def log(msg):
    print(f"[bench +{time.time()-t0:7.1f}s] {msg}", file=sys.stderr, flush=True)


SMOKE = "--cpu" in sys.argv
if SMOKE:
    os.environ["JAX_PLATFORMS"] = "cpu"

log("importing jax (first TPU contact can take minutes)...")
import jax  # noqa: E402

if SMOKE:
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

log("initializing backend / discovering devices...")
devices = jax.devices()
dev = devices[0]
platform = dev.platform
kind = getattr(dev, "device_kind", platform)
log(f"backend up: {len(devices)}x {kind} ({platform})")

# bf16 peak FLOP/s by device kind (public spec sheets; conservative default)
PEAKS = {
    "v4": 275e12,
    "v5 lite": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v5": 459e12,
    "v6 lite": 918e12, "v6e": 918e12, "trillium": 918e12,
}


def chip_peak(kind: str) -> float | None:
    k = kind.lower()
    for key in ("v6 lite", "v6e", "trillium", "v5 lite", "v5e", "v5p",
                "v5", "v4"):
        if key in k:
            return PEAKS[key]
    return None


peak = chip_peak(kind)

# Timing methodology, kept from the rounds this script was last run in:
# the sync point is a host VALUE FETCH. Every measurement (1) runs its loop
# device-side inside one executable, (2) uses inputs not seen before, and
# (3) is bracketed by scalar fetches, with the fetch round trip measured
# and subtracted.


def sync_fetch(x) -> float:
    return float(jnp.asarray(x).sum())


def measure_rtt() -> float:
    # MIN of several samples: sync latency noise is strictly additive, and
    # an inflated RTT would over-subtract from every measurement below
    z = jnp.zeros(())
    sync_fetch(z)
    samples = []
    for i in range(5):
        t = time.time()
        sync_fetch(z + float(i + 1))
        samples.append(time.time() - t)
    return min(samples)


RTT = measure_rtt()
log(f"host<->device sync round-trip: {RTT*1e3:.1f}ms")


def peak_hbm_gb() -> float | None:
    try:
        stats = dev.memory_stats()
        return round(stats["peak_bytes_in_use"] / 1e9, 2)
    except Exception:
        return None


# ------------------------------------------------------------ (a) matmul
N = 1024 if SMOKE else 8192
log(f"matmul bench: {N}^3 bf16...")
key = jax.random.PRNGKey(0)
a = jax.random.normal(key, (N, N), jnp.bfloat16)
# scale so chained products stay in bf16 range (x <- x @ b each iter)
b = (jax.random.normal(key, (N, N)) / np.sqrt(N)).astype(jnp.bfloat16)
iters = 3 if SMOKE else 100

@jax.jit
def mm_chain(x, b):
    return jax.lax.fori_loop(0, iters, lambda i, x: x @ b, x)

sync_fetch(mm_chain(a, b))  # compile + warm
best_dt = None
for rep in range(1 if SMOKE else 3):  # best-of-3: RTT jitter is additive
    a2 = a + 0.01 * (rep + 1)  # fresh input: defeat call memoization
    t = time.time()
    sync_fetch(mm_chain(a2, b))
    dt = max(time.time() - t - RTT, 1e-9) / iters
    best_dt = dt if best_dt is None else min(best_dt, dt)
matmul_tflops = 2 * N**3 / best_dt / 1e12
log(f"matmul: {matmul_tflops:.1f} TFLOP/s"
    + (f" ({100*matmul_tflops*1e12/peak:.0f}% of {peak/1e12:.0f}T nominal)" if peak else ""))
# MFU denominator: at least the demonstrated matmul rate — if the chip beats
# the nominal table (kind string didn't match the real part), trust hardware.
peak = max(peak or 0.0, matmul_tflops * 1e12)

# ------------------------------------------------------------ (b) LLaMA step
import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import (  # noqa: E402
    LlamaConfig,
    LlamaForCausalLM,
)

if SMOKE:
    cfg = LlamaConfig(vocab_size=512, hidden_size=128, intermediate_size=256,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=256)
    BATCH, SEQ, STEPS = 2, 128, 3
else:
    # sized for one v5e chip (16G HBM) with AdamW fp32 state: ~440M params
    # -> 0.9G bf16 + 1.8G master + 3.5G moments + ~4.5G activations
    cfg = LlamaConfig(vocab_size=32000, hidden_size=1536,
                      intermediate_size=4096, num_hidden_layers=12,
                      num_attention_heads=12, max_position_embeddings=1536)
    BATCH, SEQ, STEPS = 4, 1536, 10


def llama_train_bench(cfg, batch, seq, steps, reps, label, fused=False,
                      **adamw_kwargs):
    """One compiled-TrainStep measurement. ``fused=True`` trains through
    model(ids, labels=ids) — the blockwise fused lm-head+CE path (no
    (B,S,V) logits buffer); False uses the criterion over materialized
    logits. On-chip A/B at r5: unfused is ~4.6% faster at 438M/32K-vocab
    (the extra backward lm-head matmul ≈ the saved logits traffic), fused
    is ~1% faster AND ~1.5GB lighter at 1.28B — each section uses its
    winner. Returns (tokens/s, step seconds, n_params, last loss)."""
    from paddle_tpu.models import LlamaPretrainingCriterion

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    log(f"{label}: {n_params/1e6:.1f}M params bf16 "
        f"(h={cfg.hidden_size} L={cfg.num_hidden_layers} "
        f"batch={batch} seq={seq} recompute={cfg.use_recompute} "
        f"fused_ce={fused})")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True, **adamw_kwargs)
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    if fused:
        # model called with labels positionally -> fused loss IS the output
        step = paddle.jit.TrainStep(model, lambda loss: loss, opt)
        run = lambda: step.run(ids, None, None, ids, steps=steps)
    else:
        crit = LlamaPretrainingCriterion()
        step = paddle.jit.TrainStep(
            model, lambda logits, lab: crit(logits, lab), opt)
        run = lambda: step.run(ids, labels=ids, steps=steps)
    log(f"{label}: compiling multi-step TrainStep program...")
    warm = np.asarray(run()._value)
    log(f"{label}: compiled; warmup losses {warm[0]:.3f} -> {warm[-1]:.3f}")
    samples = []
    loss = None
    for rep in range(reps):
        t = time.time()
        losses = run()
        loss = float(np.asarray(losses._value)[-1])  # value fetch = sync
        samples.append(max(time.time() - t - RTT, 1e-9) / steps)
    dt = sorted(samples)[len(samples) // 2]
    return batch * seq / dt, dt, n_params, loss


def llama_mfu(cfg, seq, n_params, tokens_per_sec):
    # PaLM-style MFU: 6N matmul flops/token + attention 12*L*h*s
    fpt = 6 * n_params + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    return tokens_per_sec * fpt / peak, fpt


tokens_per_sec, dt, n_params, loss = llama_train_bench(
    cfg, BATCH, SEQ, STEPS, 1 if SMOKE else 3, "llama-438M")
mfu, flops_per_token = llama_mfu(cfg, SEQ, n_params, tokens_per_sec)
mfu_vs_matmul = tokens_per_sec * flops_per_token / (matmul_tflops * 1e12)
log(f"llama-438M: step={dt*1e3:.1f}ms tokens/s={tokens_per_sec:,.0f} "
    f"MFU={100*mfu:.1f}% (vs in-run matmul {100*mfu_vs_matmul:.1f}%) "
    f"loss={loss:.3f}")

# ------------------------------------------------- (b2) LLaMA ~1.3B step
# The largest LLaMA that fits one 16GB chip with honest state: bf16 params
# (2.6G) + fp32 masters (5.1G) + BF16 Adam moments (5.1G, acc_dtype) +
# per-layer recompute (VERDICT r4 item 3). Guarded: an OOM must not sink
# the rest of the bench.
llama_large = {}
try:
    if SMOKE:
        lcfg = LlamaConfig(vocab_size=512, hidden_size=128,
                           intermediate_size=256, num_hidden_layers=2,
                           num_attention_heads=4,
                           max_position_embeddings=256, use_recompute=True,
                           tie_word_embeddings=True)
        LB, LS, LSTEPS = 2, 128, 2
    else:
        lcfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                           intermediate_size=5504, num_hidden_layers=24,
                           num_attention_heads=16,
                           max_position_embeddings=2048, use_recompute=True,
                           tie_word_embeddings=True)
        LB, LS, LSTEPS = 2, 2048, 4
    l_tok_s, l_dt, l_params, l_loss = llama_train_bench(
        lcfg, LB, LS, LSTEPS, 1 if SMOKE else 2, "llama-large",
        fused=True, acc_dtype="bfloat16")
    l_mfu, l_fpt = llama_mfu(lcfg, LS, l_params, l_tok_s)
    hbm = peak_hbm_gb()
    llama_large = {
        "llama_large_params_m": round(l_params / 1e6, 1),
        "llama_large_mfu_pct": round(100 * l_mfu, 2),
        "llama_large_tokens_per_sec": round(l_tok_s, 1),
        "llama_large_step_ms": round(l_dt * 1e3, 2),
        "llama_large_mfu_vs_in_run_matmul_pct": round(
            100 * l_tok_s * l_fpt / (matmul_tflops * 1e12), 2),
        "llama_large_peak_hbm_gb": hbm,
        # recompute overhead proxy: large-model flops-throughput vs 438M's
        # (recompute adds ~1 extra forward => ideal ratio ~0.75 of the
        # no-recompute MFU before memory effects)
        "llama_large_vs_438m_mfu_ratio": round(l_mfu / mfu, 3) if mfu else None,
    }
    log(f"llama-large: step={l_dt*1e3:.0f}ms tokens/s={l_tok_s:,.0f} "
        f"MFU={100*l_mfu:.1f}% peak-HBM={hbm}GB "
        f"(ratio vs 438M MFU {llama_large['llama_large_vs_438m_mfu_ratio']})")
except Exception as e:  # OOM / compile failure must not sink the bench
    log(f"llama-large section FAILED: {type(e).__name__}: {e}")
    llama_large = {"llama_large_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------------------ (c) resnet
# BASELINE config 1: resnet50 training throughput (img/s) on synthetic
# CIFAR-shaped data through TrainStep.run; resnet18 kept for
# round-over-round continuity of the r2-r4 record.
from paddle_tpu.vision import models as _vmodels  # noqa: E402
import paddle_tpu.nn as _nn  # noqa: E402


def resnet_bench(factory, name, batch, steps, reps):
    paddle.seed(0)
    rn = factory(num_classes=10)
    rn_opt = paddle.optimizer.Momentum(learning_rate=0.1,
                                       parameters=rn.parameters())
    rn_crit = _nn.CrossEntropyLoss()
    x = paddle.to_tensor(np.random.rand(batch, 3, 32, 32).astype(np.float32))
    y = paddle.to_tensor(np.random.randint(0, 10, (batch, 1)))
    rn_step = paddle.jit.TrainStep(rn, lambda out: rn_crit(out, y), rn_opt)
    log(f"{name}: compiling (batch={batch} steps/dispatch={steps})...")
    sync_fetch(rn_step.run(x, steps=steps)._value)
    rtt = measure_rtt()  # steady-state RTT for the small-model timing
    samples = []
    for rep in range(reps):
        t = time.time()
        losses = rn_step.run(x, steps=steps)
        sync_fetch(losses._value)
        samples.append(max(time.time() - t - rtt, 1e-9) / steps)
    dt = sorted(samples)[len(samples) // 2]
    log(f"{name}: {dt*1e3:.1f}ms/step {batch/dt:,.0f} img/s")
    return batch / dt


if SMOKE:
    RN_BATCH, RN_STEPS, RN_REPS = 8, 2, 1
else:
    RN_BATCH, RN_STEPS, RN_REPS = 256, 400, 3
resnet50_img_s = resnet_bench(_vmodels.resnet50, "resnet50", RN_BATCH,
                              RN_STEPS if SMOKE else 100, RN_REPS)
resnet18_img_s = resnet_bench(_vmodels.resnet18, "resnet18", RN_BATCH,
                              RN_STEPS, RN_REPS)

# ------------------------------------------------------- (c2) BERT fused
# BASELINE config 2: BERT-base with the fused attention/feedforward path
# (incubate FusedTransformerEncoderLayer -> Pallas flash attention).
bert_metrics = {}
try:
    from paddle_tpu.models.bert import (
        BertForPretraining, BertPretrainingCriterion, bert_base_config,
        bert_tiny_config,
    )

    if SMOKE:
        bcfg = bert_tiny_config()
        BB, BS, BSTEPS, BREPS = 2, 64, 2, 1
    else:
        bcfg = bert_base_config(hidden_dropout_prob=0.0,
                                attention_probs_dropout_prob=0.0)
        BB, BS, BSTEPS, BREPS = 32, 128, 10, 3
    paddle.seed(0)
    bert = BertForPretraining(bcfg)
    bert.to(dtype="bfloat16")
    b_params = sum(int(np.prod(p.shape)) for p in bert.parameters())
    b_opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                   parameters=bert.parameters(),
                                   multi_precision=True)
    b_crit = BertPretrainingCriterion()
    b_ids = paddle.to_tensor(
        np.random.randint(0, bcfg.vocab_size, (BB, BS)).astype(np.int32))
    b_mlm = paddle.to_tensor(
        np.random.randint(0, bcfg.vocab_size, (BB, BS)).astype(np.int32))
    b_nsp = paddle.to_tensor(np.random.randint(0, 2, (BB, 1)))
    b_step = paddle.jit.TrainStep(
        bert, lambda mlm, nsp: b_crit(mlm, nsp, b_mlm, b_nsp), b_opt)
    log(f"bert-base: {b_params/1e6:.1f}M params, compiling "
        f"(batch={BB} seq={BS})...")
    sync_fetch(b_step.run(b_ids, steps=BSTEPS)._value)
    samples = []
    for rep in range(BREPS):
        t = time.time()
        losses = b_step.run(b_ids, steps=BSTEPS)
        sync_fetch(losses._value)
        samples.append(max(time.time() - t - RTT, 1e-9) / BSTEPS)
    b_dt = sorted(samples)[len(samples) // 2]
    bert_tok_s = BB * BS / b_dt
    b_fpt = 6 * b_params + 12 * bcfg.num_hidden_layers * bcfg.hidden_size * BS
    b_mfu = bert_tok_s * b_fpt / peak
    bert_metrics = {
        "bert_base_tokens_per_sec": round(bert_tok_s, 1),
        "bert_base_step_ms": round(b_dt * 1e3, 2),
        "bert_base_mfu_pct": round(100 * b_mfu, 2),
        "bert_base_mfu_vs_in_run_matmul_pct": round(
            100 * bert_tok_s * b_fpt / (matmul_tflops * 1e12), 2),
    }
    log(f"bert-base: step={b_dt*1e3:.1f}ms tokens/s={bert_tok_s:,.0f} "
        f"MFU={100*b_mfu:.1f}%")
except Exception as e:
    log(f"bert section FAILED: {type(e).__name__}: {e}")
    bert_metrics = {"bert_base_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------------------ (d) decode
# Serving-path kernel throughput: Pallas paged_attention at batch 8 over a
# 4K-token paged KV cache (the block_multi_head_attention analog). The
# kernel is scanned device-side over DEC_STEPS fresh queries so the number
# is cache-bandwidth throughput, not dispatch latency.
#
# Methodology (round-4 hardening, after the r3 capture proved unrepeatable):
#   1. In-run CALIBRATION: a plain-XLA streaming reduction over the SAME
#      page arrays, 3 reps, median -> the environment's streaming floor.
#   2. The decode program is AOT-compiled ONCE (lower().compile()); timed
#      calls invoke the compiled executable, so recompilation between warm
#      and timed runs is structurally impossible.
#   3. TWO warm executions with fresh inputs (the first real execution
#      absorbs deferred work a value-fetch doesn't sync), then
#      >=5 timed reps with fresh inputs; the MEDIAN is reported, min/max
#      recorded for transparency.
#   4. Residency check: page buffers are committed device arrays before
#      any timed run.
from paddle_tpu.ops.pallas.decode_attention import paged_attention  # noqa: E402

if SMOKE:
    DB, DH, DKVH, DD, DKV, PAGE, DEC_STEPS = 2, 4, 4, 64, 256, 64, 4
else:
    # 256 scanned steps: the whole timed dispatch (~90ms at 350us/step)
    # must dominate the sync RTT on congested days or the subtraction is
    # noise (r5 run 1: a 64-step rep clamped below the 112ms RTT)
    DB, DH, DKVH, DD, DKV, PAGE, DEC_STEPS = 8, 32, 8, 128, 4096, 128, 256
pages_per_seq = DKV // PAGE
npages = DB * pages_per_seq
log(f"decode bench: batch={DB} heads={DH} kv_heads={DKVH} d={DD} "
    f"KV={DKV} page={PAGE}...")
k_pages = jax.random.normal(key, (npages, PAGE, DKVH, DD), jnp.bfloat16)
v_pages = jax.random.normal(key, (npages, PAGE, DKVH, DD), jnp.bfloat16)
tables = jnp.asarray(
    np.random.permutation(npages).reshape(DB, pages_per_seq), jnp.int32)
dlens = jnp.full((DB,), DKV, jnp.int32)
cache_bytes = 2 * DB * DKV * DKVH * DD * 2  # bf16, read once per step

# (d.1) calibration: what does a plain XLA streaming read of the same
# bytes cost in this process right now? Scanned device-side (CAL_ITERS
# full passes per dispatch) so the measurement resolves even when the
# read is far below the sync RTT jitter.
CAL_ITERS = 2 if SMOKE else 20

@jax.jit
def stream_reduce(k, v, s0):
    # abs(x + s) is NOT algebraically factorable (sum(k*s) = s*sum(k)
    # would let XLA hoist the whole read out of the loop — observed as a
    # >HBM-peak "floor"), so every iteration must stream the full arrays
    def body(s, _):
        r = (jnp.abs(k.astype(jnp.float32) + s).sum()
             + jnp.abs(v.astype(jnp.float32) + s).sum())
        return s + r * 1e-30, None

    s, _ = jax.lax.scan(body, s0, None, length=CAL_ITERS)
    return s

sync_fetch(stream_reduce(k_pages, v_pages, jnp.float32(1.0)))
floor_samples = []
for rep in range(3):
    t = time.time()
    sync_fetch(stream_reduce(k_pages, v_pages, jnp.float32(2.0 + rep)))
    floor_samples.append(max(time.time() - t - RTT, 1e-9) / CAL_ITERS)
floor_dt = sorted(floor_samples)[len(floor_samples) // 2]
floor_gbs = cache_bytes / floor_dt / 1e9
log(f"streaming-read calibration: {floor_dt*1e3:.1f}ms for "
    f"{cache_bytes/1e6:.0f}MB -> floor {floor_gbs:.1f} GB/s "
    f"(equiv decode floor {DB*floor_gbs*1e9/cache_bytes:,.0f} tok/s)")

# (d.2) residency: pages must be committed device arrays before timing
for name, arr in (("k_pages", k_pages), ("v_pages", v_pages),
                  ("tables", tables)):
    devs = getattr(arr, "devices", lambda: set())()
    assert devs and all(d.platform == platform for d in devs), \
        f"{name} not device-resident: {devs}"


def decode_scan_fn(qs, k_pages, v_pages):
    # cache rides as arguments: closure-captured arrays are baked into the
    # executable as constants (and this setup's remote-compile rejects
    # >100MB programs outright)
    def body(acc, q):
        out = paged_attention(q, k_pages, v_pages, tables, dlens)
        return acc + out.astype(jnp.float32).sum(), None

    acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), qs)
    return acc


qs = jax.random.normal(key, (DEC_STEPS, DB, DH, DD), jnp.bfloat16)
# AOT: one executable, reused for every warm + timed call -> no recompile
decode_exec = jax.jit(decode_scan_fn).lower(qs, k_pages, v_pages).compile()
sync_fetch(decode_exec(qs, k_pages, v_pages))          # warm 1
sync_fetch(decode_exec(qs + 0.5, k_pages, v_pages))    # warm 2 (fresh input)
dec_samples = []
for rep in range(2 if SMOKE else 5):
    t = time.time()
    sync_fetch(decode_exec(qs + 0.01 * (rep + 1), k_pages, v_pages))
    dec_samples.append(max(time.time() - t - RTT, 1e-9) / DEC_STEPS)
dec_sorted = sorted(dec_samples)
dec_dt = dec_sorted[len(dec_sorted) // 2]  # median
decode_tok_s = DB / dec_dt
dec_gbs = cache_bytes / dec_dt / 1e9
log(f"paged decode attention: median {dec_dt*1e6:.0f}us/step "
    f"(min {dec_sorted[0]*1e6:.0f} max {dec_sorted[-1]*1e6:.0f})  "
    f"{decode_tok_s:,.0f} tok/s (batch {DB}, KV {DKV})  "
    f"cache read {dec_gbs:.1f} GB/s  vs floor {dec_gbs/floor_gbs:.2f}x")

# ------------------------------------------------------- (e) model decode
# Whole-model serving throughput: generate() with the compiled decode loop
# (prefill program + ONE scanned decode program over donated paged KV
# caches — the fused_multi_transformer decode-loop analog) on the same
# 438M LLaMA, batch 8. Median of 3 timed calls with fresh prompts.
from paddle_tpu.models.generation import generate as _generate  # noqa: E402

log("rebuilding 438M model for decode (the train instance was donated)...")
paddle.seed(0)
model = LlamaForCausalLM(cfg)
model.to(dtype="bfloat16")

if SMOKE:
    GB, GS, GNEW = 2, 8, 8
else:
    GB, GS, GNEW = 8, 16, 64
log(f"model decode bench: batch={GB} prompt={GS} new={GNEW} (paged cache)...")
model.eval()
prompt = paddle.to_tensor(
    np.random.randint(0, cfg.vocab_size, (GB, GS)).astype(np.int32))
t = time.time()
_generate(model, prompt, max_new_tokens=GNEW, cache="paged")
log(f"decode programs compiled+warm in {time.time()-t:.1f}s")
gen_samples = []
for rep in range(1 if SMOKE else 3):
    fresh = paddle.to_tensor(np.random.randint(
        0, cfg.vocab_size, (GB, GS)).astype(np.int32))
    t = time.time()
    out = _generate(model, fresh, max_new_tokens=GNEW, cache="paged")
    np.asarray(out._value)  # host fetch = sync
    gen_samples.append(max(time.time() - t - RTT, 1e-9))
gen_dt = sorted(gen_samples)[len(gen_samples) // 2]
model_decode_tok_s = GB * GNEW / gen_dt
log(f"model decode: {gen_dt*1e3:.0f}ms for {GNEW} tokens x batch {GB} -> "
    f"{model_decode_tok_s:,.0f} tok/s ({gen_dt/GNEW*1e3:.1f}ms/token-step)")

# ------------------------------------------- (e2) continuous batching
# Sustained mixed-length serving through the slot scheduler (vLLM-style
# admit/retire between compiled decode segments over the paged pool) —
# beyond the reference's in-tree serving (VERDICT r4 item 9).
cb_metrics = {}
try:
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    if SMOKE:
        CB_SLOTS, CB_LEN, CB_REQ, CB_NEW, CB_SEG = 2, 128, 3, 6, 3
    else:
        # segment=32: each decode-segment dispatch (~80ms of device work)
        # must dominate the sync round trip or the number measures latency
        CB_SLOTS, CB_LEN, CB_REQ, CB_NEW, CB_SEG = 8, 512, 24, 64, 32
    log(f"continuous batching: {CB_REQ} mixed-length requests, "
        f"{CB_SLOTS} slots, segment={CB_SEG}...")
    # two buckets: each (bucket x group-width) costs one fixed-shape
    # prefill compile —
    # 32/128 still covers the 8..119 mixed-length draw below
    cb_kw = dict(max_slots=CB_SLOTS, max_len=CB_LEN, page_size=128,
                 prompt_buckets=(32, 128))
    eng = ContinuousBatchingEngine(model, **cb_kw)
    log("continuous batching: AOT warmup (every bucket x width prefill + "
        "segment program)...")
    winfo = eng.warmup(segment=CB_SEG)
    log(f"warmup compiled {winfo['programs']} programs in "
        f"{winfo['seconds']:.1f}s")
    rng_cb = np.random.RandomState(7)
    # one tiny warm run absorbs first-dispatch overheads the AOT
    # warmup cannot (executable upload, page-pool residency)
    warm_reqs = [rng_cb.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                 for n in ((5, 40) if SMOKE else (12, 60))]
    eng.run(warm_reqs, max_new_tokens=2, segment=CB_SEG)
    # A/B: the SAME length draw, fresh token values per arm (inputs not
    # seen before — bench header)
    lens = rng_cb.randint(8, 64 if SMOKE else 120, CB_REQ)
    mk_reqs = lambda: [
        rng_cb.randint(0, cfg.vocab_size, (int(n),)).astype(np.int32)
        for n in lens]
    # the serial scheduler as the A side, on a warmed engine of its own
    s_eng = ContinuousBatchingEngine(model, pipeline=False, **cb_kw)
    s_eng.warmup(segment=CB_SEG)
    s_outs, s_stats = s_eng.run(mk_reqs(), max_new_tokens=CB_NEW,
                                segment=CB_SEG)
    del s_eng
    outs, stats = eng.run(mk_reqs(), max_new_tokens=CB_NEW, segment=CB_SEG)
    assert all(o is not None and len(o) == CB_NEW for o in outs)
    assert all(o is not None and len(o) == CB_NEW for o in s_outs)
    # host overhead: host-side gap between segments (bookkeeping the
    # pipelined scheduler hides under device compute) as % of wall
    overhead_pct = lambda st: round(
        100 * st["host_gap_total_s"] / st["wall_s"], 2)
    cb_metrics = {
        "continuous_tokens_per_sec": round(stats["tokens_per_sec"], 1),
        "continuous_serial_tokens_per_sec": round(
            s_stats["tokens_per_sec"], 1),
        "continuous_pipeline_speedup": round(
            stats["tokens_per_sec"] / s_stats["tokens_per_sec"], 3)
            if s_stats["tokens_per_sec"] else None,
        "continuous_host_overhead_pct": overhead_pct(stats),
        "continuous_serial_host_overhead_pct": overhead_pct(s_stats),
        "continuous_host_gap_ms": round(stats["host_gap_ms"], 3),
        "continuous_mean_occupancy": round(stats["mean_occupancy"], 3),
        "continuous_segments": stats["segments"],
        "continuous_warmup_programs": winfo["programs"],
        "continuous_warmup_s": round(winfo["seconds"], 1),
    }
    log(f"continuous batching: {stats['tokens_per_sec']:,.0f} sustained "
        f"tok/s pipelined vs {s_stats['tokens_per_sec']:,.0f} serial "
        f"({cb_metrics['continuous_pipeline_speedup']}x) over "
        f"{stats['segments']} segments (occupancy "
        f"{stats['mean_occupancy']:.2f}, host overhead "
        f"{cb_metrics['continuous_host_overhead_pct']}% pipelined / "
        f"{cb_metrics['continuous_serial_host_overhead_pct']}% serial)")
except Exception as e:
    log(f"continuous batching section FAILED: {type(e).__name__}: {e}")
    cb_metrics = {"continuous_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------- (e3) replica fleet
# Router tier over N engine replicas (health-gated dispatch, bit-exact
# failover): the acceptance gate is ROUTER OVERHEAD — time spent in
# routing/bookkeeping outside the replica frontends must stay < 5% of
# request wall time (fleet_router_overhead_pct).
fleet_metrics = {}
try:
    from paddle_tpu.models.frontend import ServingFrontend
    from paddle_tpu.models.router import ServingRouter
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    if SMOKE:
        FL_REPS, FL_SLOTS, FL_REQ, FL_NEW, FL_SEG = 2, 2, 6, 6, 3
        FL_BUCKETS = (32,)
    else:
        FL_REPS, FL_SLOTS, FL_REQ, FL_NEW, FL_SEG = 2, 4, 16, 32, 16
        FL_BUCKETS = (32,)
    log(f"replica fleet: {FL_REPS} replicas x {FL_SLOTS} slots, "
        f"{FL_REQ} requests, segment={FL_SEG}...")
    router = ServingRouter(max_failovers=2)
    for i in range(FL_REPS):
        f_eng = ContinuousBatchingEngine(model, max_slots=FL_SLOTS,
                                         max_len=256, page_size=128,
                                         prompt_buckets=FL_BUCKETS,
                                         seed=0)
        fe = ServingFrontend(f_eng, max_queue=64, segment=FL_SEG)
        log(f"fleet replica {i}: AOT warmup...")
        router.add_replica(fe, warmup=True)
    rng_fl = np.random.RandomState(11)
    # tiny warm pass (first-dispatch overheads, as in e2)
    for rid in [router.submit(rng_fl.randint(0, cfg.vocab_size, (12,))
                              .astype(np.int32), max_new_tokens=2)
                for _ in range(FL_REPS)]:
        pass
    router.results(wait=True, timeout_s=600)
    t_fl = time.time()
    rids = [router.submit(
        rng_fl.randint(0, cfg.vocab_size,
                       (int(rng_fl.randint(8, 28)),)).astype(np.int32),
        max_new_tokens=FL_NEW) for _ in range(FL_REQ)]
    fl_res = router.results(wait=True, timeout_s=600)
    fl_wall = time.time() - t_fl
    assert all(fl_res[r].status == "ok" for r in rids), \
        {r: fl_res[r].status for r in rids}
    fl_stats = router.stats()
    fl_tokens = sum(len(fl_res[r].tokens) for r in rids)
    fleet_metrics = {
        "fleet_replicas": FL_REPS,
        "fleet_tokens_per_sec": round(fl_tokens / fl_wall, 1)
            if fl_wall > 0 else None,
        "fleet_router_overhead_pct": round(
            fl_stats["router_overhead_pct"], 3),
        "fleet_requests_ok": fl_stats.get("requests_ok", 0),
    }
    router.shutdown()
    log(f"replica fleet: {fleet_metrics['fleet_tokens_per_sec']} tok/s "
        f"over {FL_REPS} replicas, router overhead "
        f"{fleet_metrics['fleet_router_overhead_pct']}% of active "
        f"request-processing time (gate: < 5%)")

    # -- cross-process transport gate: the same fleet shape with every
    # call crossing the hardened RPC wire (ReplicaServer behind this
    # process's dispatcher, RemoteFrontend stubs in front — encode →
    # store inbox → worker pool → reply). fleet_rpc_overhead_pct is
    # wire+serialization time (round-trip minus server-reported
    # execution) as a share of active processing, gated < 10%.
    from paddle_tpu.distributed import rpc
    from paddle_tpu.models.remote import RemoteFrontend, ReplicaServer

    log(f"rpc fleet: {FL_REPS} remote replicas over the RPC transport...")
    # a decode-heavy batch + a long results long-poll window: the
    # transport's fixed per-call cost (~ms of store round-trips) must be
    # amortized over real serving work for the % gate to measure the
    # wire, not the batch size; the server's results() returns EARLY
    # the moment rows exist, so the 1s window costs no latency
    RPC_REQ, RPC_NEW = (12, 64) if SMOKE else (FL_REQ, 2 * FL_NEW)
    rpc.init_rpc("bench", rank=0, world_size=1)
    servers = []
    try:
        r_router = ServingRouter(max_failovers=2, health_ttl=1.0)
        for i in range(FL_REPS):
            r_eng = ContinuousBatchingEngine(model, max_slots=FL_SLOTS,
                                             max_len=256, page_size=128,
                                             prompt_buckets=FL_BUCKETS,
                                             seed=0)
            r_fe = ServingFrontend(r_eng, max_queue=64, segment=FL_SEG)
            servers.append(ReplicaServer(r_fe, name=f"bench_rep{i}"))
            r_router.add_replica(
                RemoteFrontend("bench", server=f"bench_rep{i}",
                               timeout=600.0, warmup_timeout=900.0,
                               results_wait=1.0),
                warmup=True)
        # warm pass: first-traffic XLA compiles land here, so the
        # overhead window below measures steady-state transport
        warm = [r_router.submit(rng_fl.randint(0, cfg.vocab_size, (12,))
                                .astype(np.int32), max_new_tokens=2)
                for _ in range(FL_REPS)]
        r_router.results(wait=True, timeout_s=600)
        st0 = r_router.stats()
        t_rpc = time.time()
        r_rids = [r_router.submit(
            rng_fl.randint(0, cfg.vocab_size,
                           (int(rng_fl.randint(8, 28)),)).astype(np.int32),
            max_new_tokens=RPC_NEW) for _ in range(RPC_REQ)]
        r_res = r_router.results(wait=True, timeout_s=600)
        rpc_wall = time.time() - t_rpc
        st1 = r_router.stats()
        assert all(r_res[r].status == "ok" for r in r_rids), \
            {r: r_res[r].status for r in r_rids}
        d_ovh = st1["rpc_overhead_s"] - st0["rpc_overhead_s"]
        d_active = ((st1["route_s"] + st1["pump_s"])
                    - (st0["route_s"] + st0["pump_s"]))
        rpc_overhead_pct = (100.0 * d_ovh / d_active
                            if d_active > 0 else 0.0)
        rpc_tokens = sum(len(r_res[r].tokens) for r in r_rids)
        fleet_metrics.update({
            "fleet_rpc_overhead_pct": round(rpc_overhead_pct, 3),
            "fleet_rpc_tokens_per_sec": round(rpc_tokens / rpc_wall, 1)
                if rpc_wall > 0 else None,
            "fleet_rpc_calls": st1["rpc_calls"],
        })
        r_router.shutdown()
        log(f"rpc fleet: {fleet_metrics['fleet_rpc_tokens_per_sec']} "
            f"tok/s over {FL_REPS} remote replicas "
            f"({st1['rpc_calls']} rpc calls), transport overhead "
            f"{fleet_metrics['fleet_rpc_overhead_pct']}% of active "
            f"request-processing time (gate: < 10%)")
    finally:
        for srv in servers:
            if not srv.stopped.is_set():
                srv.shutdown(drain=False)
        rpc.shutdown()
except Exception as e:
    log(f"replica fleet section FAILED: {type(e).__name__}: {e}")
    # merge, don't replace: an rpc-section failure must not discard the
    # in-process gate numbers the first half already measured
    fleet_metrics["fleet_error"] = f"{type(e).__name__}: {e}"[:200]

# ------------------------------------------------- (e4) durable router
# The HA router's write-ahead request journal (models/journal.py): every
# admission durable before the rid is acked, progress checkpointed every
# K tokens, retirement GC'd. The acceptance gate is JOURNAL OVERHEAD —
# WAL encode+flush time as a share of active request-processing time,
# router_journal_overhead_pct < 5% (the durability that makes a router
# crash recoverable must not tax the hot path).
journal_metrics = {}
try:
    import shutil
    import tempfile

    from paddle_tpu.models.frontend import ServingFrontend
    from paddle_tpu.models.journal import RequestJournal
    from paddle_tpu.models.router import ServingRouter
    from paddle_tpu.models.serving import ContinuousBatchingEngine

    if SMOKE:
        # J_NEW is deliberately not tiny: the gate is RELATIVE journal
        # cost, and with only a handful of decode tokens per request
        # the per-admission fsync dominates any measurement
        J_REPS, J_SLOTS, J_REQ, J_NEW, J_SEG = 2, 2, 8, 24, 3
        J_BUCKETS = (32,)
    else:
        J_REPS, J_SLOTS, J_REQ, J_NEW, J_SEG = 2, 4, 16, 32, 16
        J_BUCKETS = (32,)
    log(f"durable router: {J_REPS} replicas, {J_REQ} requests, "
        "write-ahead journal armed...")
    j_root = tempfile.mkdtemp(prefix="bench_journal_")
    try:
        journal = RequestJournal(j_root, epoch=1)
        j_router = ServingRouter(max_failovers=2, journal=journal)
        for i in range(J_REPS):
            j_eng = ContinuousBatchingEngine(model, max_slots=J_SLOTS,
                                             max_len=256, page_size=128,
                                             prompt_buckets=J_BUCKETS,
                                             seed=0)
            j_router.add_replica(
                ServingFrontend(j_eng, max_queue=64, segment=J_SEG),
                warmup=True)
        rng_j = np.random.RandomState(17)
        warm = [j_router.submit(rng_j.randint(0, cfg.vocab_size, (12,))
                                .astype(np.int32), max_new_tokens=2)
                for _ in range(J_REPS)]
        j_router.results(wait=True, timeout_s=600)
        t_j = time.time()
        j_rids = [j_router.submit(
            rng_j.randint(0, cfg.vocab_size,
                          (int(rng_j.randint(8, 28)),)).astype(np.int32),
            max_new_tokens=J_NEW) for _ in range(J_REQ)]
        j_res = j_router.results(wait=True, timeout_s=600)
        j_wall = time.time() - t_j
        assert all(j_res[r].status == "ok" for r in j_rids), \
            {r: j_res[r].status for r in j_rids}
        j_stats = j_router.stats()
        jn = journal.stats()
        j_tokens = sum(len(j_res[r].tokens) for r in j_rids)
        journal_metrics = {
            "router_journal_overhead_pct": round(
                j_stats["journal_overhead_pct"], 3),
            "journal_tokens_per_sec": round(j_tokens / j_wall, 1)
                if j_wall > 0 else None,
            "journal_records": jn["records"],
            "journal_flushes": jn["flushes"],
            "journal_bytes": jn["bytes_written"],
        }
        j_router.shutdown()
        log(f"durable router: {journal_metrics['journal_tokens_per_sec']}"
            f" tok/s with the journal armed ({jn['records']} records, "
            f"{jn['flushes']} flushes, {jn['bytes_written']}B), journal "
            f"overhead "
            f"{journal_metrics['router_journal_overhead_pct']}% of "
            "active request-processing time (gate: < 5%)")
    finally:
        shutil.rmtree(j_root, ignore_errors=True)
except Exception as e:
    log(f"durable router section FAILED: {type(e).__name__}: {e}")
    journal_metrics = {"journal_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------- (e5) telemetry overhead
# The fleet observability layer (core/telemetry.py): request tracing +
# labeled metrics are DEFAULT-ON on the serving hot path, so their cost
# is gated — telemetry_overhead_pct (throughput delta between
# FLAGS_telemetry=0 and the default-on run, % of active processing)
# must stay < 3%. Per-op microbenches (counter bump / histogram observe
# / span) record the primitive costs the A/B aggregates.
tele_metrics = {}
try:
    from paddle_tpu.core import telemetry as _tele
    from paddle_tpu.core.flags import set_flags as _tele_setf
    from paddle_tpu.models.serving import (
        ContinuousBatchingEngine as _TeleCBE,
    )

    if SMOKE:
        T_SLOTS, T_LEN, T_REQ, T_NEW, T_SEG = 2, 128, 8, 24, 4
    else:
        T_SLOTS, T_LEN, T_REQ, T_NEW, T_SEG = 8, 512, 16, 64, 32
    log(f"telemetry overhead: {T_REQ} requests x {T_NEW} tokens, "
        "A/B FLAGS_telemetry off/on...")
    t_eng = _TeleCBE(model, max_slots=T_SLOTS, max_len=T_LEN,
                     page_size=128, prompt_buckets=(32, 128))
    t_eng.warmup(segment=T_SEG)
    rng_t = np.random.RandomState(23)
    t_lens = rng_t.randint(8, 28, T_REQ)
    mk_t = lambda: [rng_t.randint(0, cfg.vocab_size,
                                  (int(n),)).astype(np.int32)
                    for n in t_lens]
    t_eng.run(mk_t()[:2], max_new_tokens=2, segment=T_SEG)  # warm
    # interleaved A/B, best-of-2 per arm: RTT jitter is additive and
    # must not read as telemetry cost
    tok_s = {0: 0.0, 1: 0.0}
    for rep in range(2):
        for arm in (0, 1):
            _tele_setf({"FLAGS_telemetry": arm})
            _, t_st = t_eng.run(mk_t(), max_new_tokens=T_NEW,
                                segment=T_SEG)
            tok_s[arm] = max(tok_s[arm], t_st["tokens_per_sec"])
    _tele_setf({"FLAGS_telemetry": 1})
    overhead_pct = (100.0 * (1.0 - tok_s[1] / tok_s[0])
                    if tok_s[0] > 0 else 0.0)
    # primitive costs (ns/op over a tight loop)
    N_OPS = 100_000
    t_c = _tele.counter("bench.tele_tick")
    t0 = time.time()
    for _ in range(N_OPS):
        t_c.inc()
    bump_ns = (time.time() - t0) / N_OPS * 1e9
    t_h = _tele.histogram("bench.tele_lat_s")
    t0 = time.time()
    for _ in range(N_OPS):
        t_h.observe(0.01)
    observe_ns = (time.time() - t0) / N_OPS * 1e9
    t0 = time.time()
    for _ in range(N_OPS // 10):
        with _tele.span("bench.tele_span"):
            pass
    span_ns = (time.time() - t0) / (N_OPS // 10) * 1e9
    tele_metrics = {
        "telemetry_overhead_pct": round(max(overhead_pct, 0.0), 3),
        "telemetry_on_tokens_per_sec": round(tok_s[1], 1),
        "telemetry_off_tokens_per_sec": round(tok_s[0], 1),
        "telemetry_bump_ns": round(bump_ns, 1),
        "telemetry_observe_ns": round(observe_ns, 1),
        "telemetry_span_ns": round(span_ns, 1),
    }
    log(f"telemetry: {tok_s[1]:,.0f} tok/s on vs {tok_s[0]:,.0f} off -> "
        f"overhead {tele_metrics['telemetry_overhead_pct']}% of active "
        f"processing (gate: < 3%); bump {bump_ns:.0f}ns, observe "
        f"{observe_ns:.0f}ns, span {span_ns:.0f}ns")
except Exception as e:
    log(f"telemetry section FAILED: {type(e).__name__}: {e}")
    tele_metrics = {"telemetry_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------- (e6) perfwatch overhead
# The performance-observability layer (core/perfwatch.py + the jit-layer
# compile watchdog): per-phase step-time attribution, KV-occupancy
# accounting, device-memory polling, SLO burn-rate monitoring, and the
# post-warmup recompile watchdog are all DEFAULT-ON behind
# FLAGS_telemetry — same A/B methodology as e5, gate < 3% of active
# processing. The full frontend path is measured (SLO ticks + shed
# checks live there), and the compile watchdog's serving-compile count
# across the warmed A/B is recorded as perfwatch_serving_compiles —
# the zero-recompile invariant, gated nonzero-fails by
# tools/bench_trend.py (GATES) over the recorded rounds.
pw_metrics = {}
try:
    from paddle_tpu.core import telemetry as _pw_tele
    from paddle_tpu.core.flags import set_flags as _pw_setf
    from paddle_tpu.models.frontend import ServingFrontend as _PwFE
    from paddle_tpu.models.serving import (
        ContinuousBatchingEngine as _PwCBE,
    )

    if SMOKE:
        P_SLOTS, P_LEN, P_REQ, P_NEW, P_SEG = 2, 128, 8, 24, 4
    else:
        P_SLOTS, P_LEN, P_REQ, P_NEW, P_SEG = 8, 512, 16, 64, 32
    log(f"perfwatch overhead: {P_REQ} requests x {P_NEW} tokens through "
        "the frontend, A/B FLAGS_telemetry off/on...")
    p_eng = _PwCBE(model, max_slots=P_SLOTS, max_len=P_LEN,
                   page_size=128, prompt_buckets=(32, 128))
    p_fe = _PwFE(p_eng, max_queue=2 * P_REQ, segment=P_SEG)
    p_fe.warmup()  # arms the compile watchdog (serving phase begins)
    rng_p = np.random.RandomState(29)
    p_lens = rng_p.randint(8, 28, P_REQ)
    mk_p = lambda: [rng_p.randint(0, cfg.vocab_size,
                                  (int(n),)).astype(np.int32)
                    for n in p_lens]
    for p in mk_p()[:2]:  # warm pass (first-dispatch overheads)
        p_fe.submit(p, max_new_tokens=2)
    p_fe.results(wait=True, timeout=600)
    c_before = _pw_tele.counter("xla.compiles_total").value(
        phase="serving")
    p_tok_s = {0: 0.0, 1: 0.0}
    for rep in range(2):  # interleaved best-of-2 per arm (RTT jitter)
        for arm in (0, 1):
            _pw_setf({"FLAGS_telemetry": arm})
            t_arm = time.time()
            p_rids = [p_fe.submit(p, max_new_tokens=P_NEW)
                      for p in mk_p()]
            p_res = p_fe.results(wait=True, timeout=600)
            arm_wall = time.time() - t_arm
            assert all(p_res[r].status == "ok" for r in p_rids), \
                {r: p_res[r].status for r in p_rids}
            toks = sum(len(p_res[r].tokens) for r in p_rids)
            p_tok_s[arm] = max(p_tok_s[arm], toks / arm_wall)
    _pw_setf({"FLAGS_telemetry": 1})
    pw_overhead_pct = (100.0 * (1.0 - p_tok_s[1] / p_tok_s[0])
                       if p_tok_s[0] > 0 else 0.0)
    serving_compiles = (_pw_tele.counter("xla.compiles_total").value(
        phase="serving") - c_before)
    p_phases = p_eng.stats()["phases"]
    pw_metrics = {
        "perfwatch_overhead_pct": round(max(pw_overhead_pct, 0.0), 3),
        "perfwatch_on_tokens_per_sec": round(p_tok_s[1], 1),
        "perfwatch_off_tokens_per_sec": round(p_tok_s[0], 1),
        "perfwatch_serving_compiles": int(serving_compiles),
        "perfwatch_segment_dispatch_us_p50": round(
            1e6 * p_phases.get("segment_dispatch", {}).get("p50", 0.0), 1),
        "perfwatch_device_wait_us_p50": round(
            1e6 * p_phases.get("device_wait", {}).get("p50", 0.0), 1),
        "perfwatch_host_bookkeeping_us_p50": round(
            1e6 * p_phases.get("host_bookkeeping", {}).get("p50", 0.0), 1),
    }
    p_fe.shutdown(drain=True)
    if serving_compiles:
        log(f"perfwatch: INVARIANT VIOLATION — {serving_compiles} "
            "post-warmup XLA recompile(s) on the serving path (expected "
            "0; see the flight-*-recompile.json dump; bench_trend gates "
            "this nonzero)")
    log(f"perfwatch: {p_tok_s[1]:,.0f} tok/s on vs {p_tok_s[0]:,.0f} off "
        f"-> overhead {pw_metrics['perfwatch_overhead_pct']}% of active "
        f"processing (gate: < 3%); post-warmup serving compiles "
        f"{serving_compiles} (invariant: 0, gated in bench_trend); "
        f"phase p50s "
        f"dispatch={pw_metrics['perfwatch_segment_dispatch_us_p50']}us "
        f"wait={pw_metrics['perfwatch_device_wait_us_p50']}us "
        f"bookkeep={pw_metrics['perfwatch_host_bookkeeping_us_p50']}us")
except Exception as e:
    log(f"perfwatch section FAILED: {type(e).__name__}: {e}")
    pw_metrics = {"perfwatch_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------- (e7) overload control
# The closed-loop overload plane (models/autoscale.py brownout ladder +
# SLO-driven autoscaler) under a synthetic flash crowd
# (tools/trafficgen.py): a 1-replica fleet takes a 10x arrival spike,
# the burn alarm flips, the autoscaler warms and admits a replica, the
# brownout ladder steps up and then FULLY recovers. Gated numbers:
# autoscaler reaction time (alarm -> new replica serving), overshoot
# (peak replicas beyond the 2 needed), brownout goodput floor +
# protected-class loss, full recovery, and the decision loop's own
# overhead < 3% of active processing.
ov_metrics = {}
try:
    from paddle_tpu.core import perfwatch as _ov_pw
    from paddle_tpu.models.autoscale import AutoScaler as _OvScaler
    from paddle_tpu.models.frontend import ServingFrontend as _OvFE
    from paddle_tpu.models.router import ServingRouter as _OvRouter
    from paddle_tpu.models.serving import (
        ContinuousBatchingEngine as _OvCBE,
    )
    from paddle_tpu.tools.trafficgen import TrafficGen, TrafficProfile

    if SMOKE:
        OV_SLOTS, OV_SEG, OV_CALM = 2, 4, 6
        OV_RPS, OV_MULT, OV_FLASH_AT, OV_FLASH_DUR, OV_DUR = \
            2.0, 15.0, 1.0, 4.0, 6.0
    else:
        OV_SLOTS, OV_SEG, OV_CALM = 4, 8, 8
        OV_RPS, OV_MULT, OV_FLASH_AT, OV_FLASH_DUR, OV_DUR = \
            4.0, 15.0, 1.0, 5.0, 8.0
    OV_FLOOR_TARGET = 0.25  # min acceptable ok/submitted over the crowd
    log(f"overload control: flash crowd {OV_MULT:g}x over "
        f"{OV_RPS:g} rps against 1 replica (autoscaler max 3)...")
    # self-calibrated SLO threshold: measure CALM per-request wall time
    # first, declare TTFT objective a multiple of it — the crowd's
    # queue wait blows it on any platform without hand-tuned seconds
    ov_mon = _ov_pw.SLOMonitor(
        # NO objectives during calibration (objectives=None would
        # install the hand-tuned defaults, and a slow container could
        # trip them — escalating the ladder mid-calibration and
        # corrupting the calibrated numbers); the real objective is
        # installed below once calm_req_s is measured
        objectives=[],
        windows=(1.0, 3.0), burn_threshold=2.0, min_count=4)
    ov_bo = _ov_pw.BrownoutController(ov_mon, hold_s=0.75, enabled=True)

    def ov_fe():
        e = _OvCBE(model, max_slots=OV_SLOTS, max_len=256,
                   page_size=128, prompt_buckets=(32,), seed=0)
        return _OvFE(e, max_queue=512, segment=OV_SEG, slo=ov_mon,
                     brownout=ov_bo)

    ov_router = _OvRouter(max_failovers=2)
    ov_router.add_replica(ov_fe(), warmup=True)
    rng_ov = np.random.RandomState(37)
    t_cal = time.time()
    cal_rids = []
    for _ in range(OV_CALM):  # calm, sequential: the no-queue baseline
        r = ov_router.submit(
            rng_ov.randint(0, cfg.vocab_size, (8,)).astype(np.int32),
            max_new_tokens=8)
        cal_rids.append(r)
        ov_router.results(wait=True, timeout_s=600)
    calm_req_s = (time.time() - t_cal) / OV_CALM
    # one calm SERVICE time: any request that queues behind another
    # blows it, any request hitting a free slot lands inside it — the
    # crowd reads as burn on every platform without hand-tuned seconds
    ttft_obj = max(calm_req_s, 0.005)
    # calibrate BATCHED capacity too, and compress the schedule's wall
    # clock so the flash crowd arrives ~4x faster than the fleet can
    # serve — the overload is structural on any platform instead of
    # depending on absolute request rates
    t_b = time.time()
    burst_n = 4 * OV_SLOTS
    for _ in range(burst_n):
        ov_router.submit(rng_ov.randint(0, cfg.vocab_size, (8,))
                         .astype(np.int32), max_new_tokens=8)
    ov_router.results(wait=True, timeout_s=600)
    cap_rps = burst_n / max(time.time() - t_b, 1e-6)
    ov_scale = min(1.0, (OV_RPS * OV_MULT) / (4.0 * cap_rps))
    ov_mon.objectives = [_ov_pw.Objective("ttft", "serving.ttft_s",
                                          ttft_obj, 0.9)]
    ov_mon._samples = {"ttft": []}
    ov_scaler = _OvScaler(
        ov_router, ov_fe, min_replicas=1, max_replicas=3, slo=ov_mon,
        brownout=ov_bo, interval_s=0.1, burn_consecutive=2,
        scale_out_cooldown_s=3.0, idle_after_s=3.0,
        scale_in_cooldown_s=3.0)
    ov_router.attach_autoscaler(ov_scaler)
    st_ov0 = ov_router.stats()
    gen = TrafficGen(TrafficProfile(
        duration_s=OV_DUR, base_rps=OV_RPS, diurnal_amplitude=0.3,
        diurnal_period_s=OV_DUR, flash_at_s=OV_FLASH_AT,
        flash_duration_s=OV_FLASH_DUR, flash_multiplier=OV_MULT,
        tenants={"web": 2.0, "batch": 1.0},
        priorities={0: 0.5, 1: 0.5}, prompt_len=(4, 12),
        max_new=(6, 12), vocab_size=cfg.vocab_size), seed=5)
    ov_state = {"peak_up": 1, "peak_stage": 0}
    submitted = []

    def ov_pump():
        ov_router.step()
        ups = sum(1 for rr in ov_router._replicas.values()
                  if rr.state == "up")
        ov_state["peak_up"] = max(ov_state["peak_up"], ups)
        if "alarm" not in ov_state and ov_mon.alarm():
            ov_state["alarm"] = time.time()
        if "up2" not in ov_state and ups >= 2:
            ov_state["up2"] = time.time()
        ov_state["peak_stage"] = max(ov_state["peak_stage"],
                                     ov_bo.stage)

    def ov_submit(a):
        submitted.append((ov_router.submit(
            a.prompt, max_new_tokens=a.max_new_tokens,
            priority=a.priority, tenant=a.tenant), a.priority))

    gen.drive(ov_submit, pump=ov_pump, time_scale=ov_scale)
    # drain through ov_pump (not results(wait=...)): the alarm-onset /
    # second-replica-serving timestamps the reaction metric needs are
    # observed on pump turns, and most of the crowd drains AFTER the
    # compressed arrival schedule finishes
    ov_res = {}
    t_drain = time.time()
    while ov_router.pending() and time.time() - t_drain < 600:
        ov_pump()
        ov_res.update(ov_router.results())
    ov_res.update(ov_router.results(wait=True, timeout_s=60))
    ok = sum(1 for r, _ in submitted if ov_res[r].status == "ok")
    prot = [(r, p) for r, p in submitted if p >= 1]
    prot_ok = sum(1 for r, _ in prot if ov_res[r].status == "ok")
    goodput_floor = ok / len(submitted) if submitted else 0.0
    prot_loss_pct = (100.0 * (1.0 - prot_ok / len(prot))
                     if prot else 0.0)
    # recovery: healthy fleet -> alarm clears -> ladder walks back to 0
    t_rec = time.time()
    while time.time() - t_rec < 60.0:
        ov_router.step()
        ov_bo.maybe_step()
        if not ov_mon.status()["alarm"] and ov_bo.stage == 0:
            break
        time.sleep(0.05)
    ov_pump()
    st_ov1 = ov_router.stats()
    sc = ov_scaler.stats()
    ov_active = ((st_ov1["route_s"] + st_ov1["pump_s"])
                 - (st_ov0["route_s"] + st_ov0["pump_s"]))
    reaction = (ov_state["up2"] - ov_state["alarm"]
                if "up2" in ov_state and "alarm" in ov_state else None)
    ov_metrics = {
        "autoscale_alarm_fired": int("alarm" in ov_state),
        "autoscale_scale_outs": sc["scale_outs"],
        "autoscale_overshoot_replicas": max(
            ov_state["peak_up"] - 2, 0),
        "autoscale_overhead_pct": round(
            100.0 * sc["eval_s"] / ov_active if ov_active > 0 else 0.0,
            3),
        "brownout_goodput_floor": round(goodput_floor, 3),
        "brownout_floor_breach": int(goodput_floor < OV_FLOOR_TARGET),
        "brownout_protected_loss_pct": round(prot_loss_pct, 3),
        "brownout_peak_stage": int(ov_state["peak_stage"]),
        "brownout_unrecovered": int(ov_bo.stage != 0),
        "overload_requests": len(submitted),
        "overload_ttft_objective_s": round(ttft_obj, 4),
        "overload_time_scale": round(ov_scale, 4),
    }
    if reaction is not None:
        ov_metrics["autoscale_reaction_s"] = round(reaction, 2)
    ov_router.shutdown()
    log(f"overload control: {len(submitted)} requests, alarm "
        f"{'fired' if 'alarm' in ov_state else 'DID NOT FIRE'}, "
        f"reaction {ov_metrics.get('autoscale_reaction_s', 'n/a')}s "
        f"(alarm -> 2nd replica serving, gate < 120), peak replicas "
        f"{ov_state['peak_up']} (overshoot "
        f"{ov_metrics['autoscale_overshoot_replicas']}, gate < 2), "
        f"goodput floor {goodput_floor:.2f} "
        f"(target >= {OV_FLOOR_TARGET}), protected-class loss "
        f"{prot_loss_pct:.2f}% (gate < 1%), brownout recovered="
        f"{not ov_metrics['brownout_unrecovered']}, autoscaler "
        f"overhead {ov_metrics['autoscale_overhead_pct']}% of active "
        f"(gate < 3%)")
except Exception as e:
    log(f"overload control section FAILED: {type(e).__name__}: {e}")
    ov_metrics = {"overload_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------- (e8) tensor-parallel serving
# One replica spans a TP gang over a ProcessMesh (models/tp_serving.py):
# params + paged KV pools sharded, AOT warmup per mesh, token streams
# bit-identical to the single-chip engine. Gated numbers: the host cost
# of committing dispatch operands onto the mesh (tp_dispatch_overhead_pct
# < 10% of active serving), and the member-death drill — a TP-group
# replica dies mid-decode, the router trips its breaker and fails over to
# the single-chip replica; recovery must land all results (zero lost)
# bit-identical to the uninterrupted reference inside 60s.
tp_metrics = {}
try:
    from paddle_tpu.models.frontend import ServingFrontend as _TpFE
    from paddle_tpu.models.router import ServingRouter as _TpRouter
    from paddle_tpu.models.serving import (
        ContinuousBatchingEngine as _TpCBE,
    )
    from paddle_tpu.models.tp_serving import TPShardedEngine, serving_mesh

    TP_DEG = min(2, len(jax.devices()))
    if SMOKE:
        TP_SLOTS, TP_SEG, TP_REQ, TP_NEW = 2, 4, 6, 24
    else:
        TP_SLOTS, TP_SEG, TP_REQ, TP_NEW = 4, 8, 12, 48
    log(f"tensor-parallel serving: TP degree {TP_DEG} "
        f"({len(jax.devices())} visible device(s)), {TP_REQ} requests...")
    tp_mesh = serving_mesh(TP_DEG)

    def _tp_fe():
        return _TpFE(TPShardedEngine(model, max_slots=TP_SLOTS,
                                     max_len=256, page_size=128,
                                     prompt_buckets=(32,), seed=0,
                                     mesh=tp_mesh),
                     max_queue=64, segment=TP_SEG)

    def _sc_fe():
        return _TpFE(_TpCBE(model, max_slots=TP_SLOTS, max_len=256,
                            page_size=128, prompt_buckets=(32,), seed=0),
                     max_queue=64, segment=TP_SEG)

    rng_tp = np.random.RandomState(23)
    tp_prompts = [rng_tp.randint(0, cfg.vocab_size,
                                 (int(rng_tp.randint(8, 28)),))
                  .astype(np.int32) for _ in range(TP_REQ)]

    # ---- dispatch-overhead gate: the same warmed workload through the
    # TP engine; overhead is the host time spent committing operands
    # onto the mesh as a share of the serving wall
    # explicit rids: sampling keys are rid-keyed, so the TP run, the
    # member-death drill, and the single-chip reference must share them
    # for their streams to be comparable
    tp_rids = [100 + i for i in range(TP_REQ)]
    tp_fe = _tp_fe()
    tp_fe.warmup()
    warm_r = tp_fe.submit(tp_prompts[0][:8], max_new_tokens=2)
    tp_fe.results(wait=True, timeout=600)
    put0 = tp_fe.engine.tp_stats()["put_s"]
    t_tp = time.time()
    for r, p in zip(tp_rids, tp_prompts):
        tp_fe.submit(p, max_new_tokens=TP_NEW, rid=r)
    tp_res = tp_fe.results(wait=True, timeout=600)
    tp_wall = time.time() - t_tp
    assert all(tp_res[r].status == "ok" for r in tp_rids), \
        {r: tp_res[r].status for r in tp_rids}
    tp_put = tp_fe.engine.tp_stats()["put_s"] - put0
    tp_tokens = sum(len(tp_res[r].tokens) for r in tp_rids)
    tp_metrics = {
        "tp_degree": TP_DEG,
        "tp_tokens_per_sec": round(tp_tokens / tp_wall, 1)
            if tp_wall > 0 else None,
        "tp_dispatch_overhead_pct": round(
            100.0 * tp_put / tp_wall if tp_wall > 0 else 0.0, 3),
    }
    # the single-chip reference streams for the SAME rids (the failover
    # bit-exactness oracle below)
    sc_ref = _sc_fe()
    for r, p in zip(tp_rids, tp_prompts):
        sc_ref.submit(p, max_new_tokens=TP_NEW, rid=r)
    ref_res = sc_ref.results(wait=True, timeout=600)
    sc_ref.shutdown()
    diverged = sum(
        1 for r in tp_rids
        if not np.array_equal(tp_res[r].tokens, ref_res[r].tokens))
    tp_metrics["tp_stream_divergence"] = int(diverged > 0)
    tp_fe.shutdown()
    log(f"tensor-parallel serving: {tp_metrics['tp_tokens_per_sec']} "
        f"tok/s at degree {TP_DEG}, dispatch overhead "
        f"{tp_metrics['tp_dispatch_overhead_pct']}% of serving wall "
        f"(gate < 10%), {diverged} stream(s) diverged from the "
        "single-chip reference (gate: 0)")

    # ---- member-death recovery drill: a mixed fleet (TP group + single
    # chip); the TP replica dies mid-decode; every stranded request must
    # fail over bit-identically and nothing may be lost
    d_router = _TpRouter(max_failovers=2)
    tp_id = d_router.add_replica(_tp_fe(), warmup=True)
    d_router.add_replica(_sc_fe(), warmup=True)
    d_rids = [d_router.submit(p, max_new_tokens=TP_NEW, rid=r)
              for r, p in zip(tp_rids, tp_prompts)]
    for _ in range(2):  # let decode start so the kill lands mid-stream
        d_router.step()
    t_kill = time.time()
    d_router.fail_replica(tp_id, "bench e8 member-death drill")
    d_res = d_router.results(wait=True, timeout_s=600)
    recovery_s = time.time() - t_kill
    lost = sum(1 for r in d_rids if r not in d_res
               or d_res[r].status != "ok")
    d_diverged = sum(
        1 for r in d_rids if r in d_res
        and not np.array_equal(d_res[r].tokens, ref_res[r].tokens))
    tp_metrics.update({
        "tp_member_death_recovery_s": round(recovery_s, 2),
        "tp_lost_requests": lost,
    })
    tp_metrics["tp_stream_divergence"] = int(
        tp_metrics["tp_stream_divergence"] or d_diverged > 0)
    d_router.shutdown()
    log(f"tp member-death drill: group breaker tripped, {len(d_rids)} "
        f"request(s) recovered in {recovery_s:.2f}s (gate < 60), "
        f"{lost} lost (gate: 0), {d_diverged} diverged after failover "
        "(gate: 0)")
except Exception as e:
    log(f"tensor-parallel serving section FAILED: "
        f"{type(e).__name__}: {e}")
    tp_metrics = {"tp_error": f"{type(e).__name__}: {e}"[:200]}

# --------------------------- (e9) dynamic paged KV + prefix caching
# The static slot->page map is gone: the engine grants pages from a
# free-list pool at admission and as decode grows, and shares prompt
# prefixes copy-on-write. Gated numbers: at FIXED pool bytes a
# mixed-length workload must hold >= 2x more concurrent requests than
# the static one-full-sequence-per-slot layout (kv_admit_gain), the
# granted-tail fragmentation stays bounded (kv_fragmentation_pct),
# shared-prefix prefill is measurably faster than the cold path
# (prefix_prefill_speedup >= 1 with prefix_hit_rate > 0), and the
# whole allocator path stays at ZERO post-warmup compiles
# (kv_serving_compiles).
kv_metrics = {}
try:
    from paddle_tpu.jit import count_backend_compiles
    from paddle_tpu.models.serving import (
        ContinuousBatchingEngine as _KvCBE,
    )

    if SMOKE:
        KV_LEN, KV_PAGE, KV_REQ, KV_NEW = 256, 64, 16, 8
    else:
        KV_LEN, KV_PAGE, KV_REQ, KV_NEW = 512, 128, 48, 16
    per_seq_pages = KV_LEN // KV_PAGE
    pool_pages = 4 * per_seq_pages  # the STATIC layout fits 4 slots
    rng_kv = np.random.RandomState(31)
    # mixed-length, mostly-short traffic: the shape the static map
    # wastes a full slot tail on
    kv_prompts = [rng_kv.randint(0, cfg.vocab_size,
                                 (int(rng_kv.choice([6, 10, 18, 40])),))
                  .astype(np.int32) for _ in range(KV_REQ)]

    def _kv_run(max_slots, pool=None):
        eng = _KvCBE(model, max_slots=max_slots, max_len=KV_LEN,
                     page_size=KV_PAGE, prompt_buckets=(16, 64),
                     seed=0, pool_pages=pool)
        eng.start(segment=4)
        for i, p in enumerate(kv_prompts):
            eng.submit(p, KV_NEW, rid=i)
        peak, frag, static_frag = 0, 0.0, 0.0
        while eng.has_work():
            eng.step()
            active = len(eng.active_requests())
            if active >= peak:
                peak = active
                st = eng.kv_stats()
                frag = st["fragmentation_pct"]
                # what the static one-full-sequence-per-slot layout
                # would waste on this same snapshot: every active slot
                # pins per_seq pages regardless of its length
                cap = st["bytes_in_use"] / st["bytes_per_token"]
                used = cap * (1.0 - frag / 100.0)
                static_cap = active * per_seq_pages * KV_PAGE
                static_frag = (100.0 * (1.0 - used / static_cap)
                               if static_cap else 0.0)
        return peak, frag, static_frag, eng

    log(f"dynamic paged KV: {KV_REQ} mixed-length requests over a "
        f"{pool_pages}-page pool ({KV_PAGE}-token pages)...")
    # static arm: the historical layout — every slot permanently owns a
    # full sequence of pages, so the same pool bytes cap concurrency at
    # pool/per_seq slots
    static_peak, _, _, _ = _kv_run(pool_pages // per_seq_pages)
    dyn_peak, dyn_frag, static_frag, dyn_eng = _kv_run(
        4 * pool_pages // per_seq_pages, pool=pool_pages)
    kv_metrics = {
        "kv_pool_pages": pool_pages,
        "kv_static_peak_admitted": static_peak,
        "kv_dynamic_peak_admitted": dyn_peak,
        "kv_admit_gain": round(dyn_peak / static_peak, 2)
            if static_peak else None,
        "kv_fragmentation_pct": round(dyn_frag, 2),
        "kv_static_fragmentation_pct": round(static_frag, 2),
        "kv_frag_vs_static": round(dyn_frag / static_frag, 3)
            if static_frag else None,
    }
    log(f"dynamic paged KV: peak concurrency {dyn_peak} vs {static_peak} "
        f"static at the same pool bytes "
        f"(gain {kv_metrics['kv_admit_gain']}x, gate >= 2x), granted "
        f"fragmentation {dyn_frag:.1f}% vs {static_frag:.1f}% static "
        f"(ratio {kv_metrics['kv_frag_vs_static']}, gate < 1)")

    # ---- prefix-hit sweep: all requests share a long system prompt;
    # the cached arm prefills only each request's divergent tail
    sys_p = rng_kv.randint(0, cfg.vocab_size,
                           (3 * KV_PAGE,)).astype(np.int32)
    px_prompts = [np.concatenate(
        [sys_p, rng_kv.randint(0, cfg.vocab_size, (12,)).astype(np.int32)])
        for _ in range(KV_REQ // 2)]

    def _px_run(cache_on):
        eng = _KvCBE(model, max_slots=4, max_len=2 * KV_LEN,
                     page_size=KV_PAGE, prompt_buckets=(16, 64),
                     seed=0, prefix_cache=cache_on)
        eng.warmup(segment=4)
        eng.start(segment=4)
        # seed request: its prompt pages populate (or would populate)
        # the cache before timing starts
        eng.submit(px_prompts[0], 2, rid=1000)
        while eng.has_work():
            eng.step()
        t0 = time.time()
        with count_backend_compiles() as compiles:
            for i, p in enumerate(px_prompts):
                eng.submit(p, 2, rid=i)
            while eng.has_work():
                eng.step()
        return time.time() - t0, len(compiles), eng

    cold_s, _, _ = _px_run(False)
    warm_s, px_compiles, px_eng = _px_run(True)
    px_stats = px_eng.kv_stats()
    kv_metrics.update({
        "prefix_prefill_speedup": round(cold_s / warm_s, 3)
            if warm_s > 0 else None,
        "prefix_hit_rate": round(px_stats["prefix_hit_rate"], 4),
        "prefix_tokens_saved": int(px_stats["prefix_tokens_saved"]),
        "kv_serving_compiles": int(px_compiles),
    })
    log(f"prefix caching: shared-prefix prefill {cold_s:.3f}s cold vs "
        f"{warm_s:.3f}s cached (speedup "
        f"{kv_metrics['prefix_prefill_speedup']}x, gate >= 1), hit rate "
        f"{kv_metrics['prefix_hit_rate']}, "
        f"{kv_metrics['prefix_tokens_saved']} prompt tokens saved, "
        f"{px_compiles} post-warmup compile(s) through the allocator "
        "path (gate: 0)")
except Exception as e:
    log(f"dynamic paged KV section FAILED: {type(e).__name__}: {e}")
    kv_metrics = {"kv_error": f"{type(e).__name__}: {e}"[:200]}

# --------------------- (e10) disaggregated prefill/decode serving
# Prefill and decode run on DIFFERENT replicas joined by the
# fault-tolerant KV page transfer (models/transfer.py): an A/B against
# a colocated fleet of identical capacity under the same trafficgen
# mixed long-prompt/short-decode schedule (same seed => bit-identical
# arrivals). Gated numbers: the transfer hop's own wall time stays
# < 10% of active processing (transfer_overhead_pct), client TTFT p95
# under the long-prompt burst stays within 2x of colocated
# (decode_ttft_p95_ratio — the hop must not queue first tokens behind
# the wire), and NO request is lost to the hop
# (transfer_lost_requests).
xfer_metrics = {}
try:
    from paddle_tpu.core import telemetry as _xf_tele
    from paddle_tpu.models.frontend import (
        ServingFrontend as _XfFE,
        latency_summaries as _xf_lat,
    )
    from paddle_tpu.models.router import ServingRouter as _XfRouter
    from paddle_tpu.models.serving import (
        ContinuousBatchingEngine as _XfCBE,
    )
    from paddle_tpu.tools.trafficgen import (
        TrafficGen as _XfGen,
        TrafficProfile as _XfProf,
    )

    if SMOKE:
        XF_SLOTS, XF_SEG, XF_DUR, XF_RPS = 2, 4, 3.0, 3.0
        XF_PLEN, XF_NEW = (16, 40), (2, 6)
    else:
        XF_SLOTS, XF_SEG, XF_DUR, XF_RPS = 4, 4, 6.0, 6.0
        XF_PLEN, XF_NEW = (24, 64), (2, 8)
    log("disaggregated serving: 1 prefill + 2 decode vs 3 colocated "
        f"replicas, {XF_DUR:g}s schedule at {XF_RPS:g} rps "
        "(long-prompt burst mid-schedule)...")

    def _xf_run(roles):
        # fresh registry per arm: each arm's serving.ttft_s population
        # is exactly its own requests (the decode-side import adoption
        # records NO attempt-level TTFT sample, so the disagg arm's
        # percentiles are client-visible submit -> first token)
        _xf_tele.reset_telemetry()
        router = _XfRouter(max_failovers=2)
        for role in roles:
            e = _XfCBE(model, max_slots=XF_SLOTS, max_len=128,
                       page_size=32, prompt_buckets=(16, 64), seed=0)
            router.add_replica(
                _XfFE(e, max_queue=512, segment=XF_SEG, role=role),
                warmup=True)
        gen = _XfGen(_XfProf(
            duration_s=XF_DUR, base_rps=XF_RPS, diurnal_amplitude=0.0,
            flash_at_s=XF_DUR / 3.0, flash_duration_s=XF_DUR / 3.0,
            flash_multiplier=3.0, prompt_len=XF_PLEN, max_new=XF_NEW,
            vocab_size=cfg.vocab_size), seed=17)
        st0 = router.stats()
        rids = gen.replay_into(router, time_scale=0.25)
        res = router.results(wait=True, timeout_s=600)
        st1 = router.stats()
        lost = sum(1 for r in rids if res[r].status != "ok")
        xh = _xf_tele.histogram("fleet.transfer_s").summary()
        out = {
            "requests": len(rids),
            "lost": lost,
            "ttft_p95_s": _xf_lat()["ttft_s"]["p95"],
            "active_s": ((st1["route_s"] + st1["pump_s"])
                         - (st0["route_s"] + st0["pump_s"])),
            "transfer_s": (xh["count"] or 0) * (xh["mean"] or 0.0),
            "transfers": int(_xf_tele.counter(
                "fleet.transfer_completed").value()),
        }
        router.shutdown()
        return out

    colo = _xf_run(("both", "both", "both"))
    disagg = _xf_run(("prefill", "decode", "decode"))
    assert disagg["transfers"] > 0, \
        "disaggregated arm never engaged the transfer hop"
    xfer_metrics = {
        "disagg_requests": disagg["requests"],
        "disagg_transfers_completed": disagg["transfers"],
        "transfer_lost_requests": disagg["lost"] + colo["lost"],
        "transfer_overhead_pct": round(
            100.0 * disagg["transfer_s"] / disagg["active_s"]
            if disagg["active_s"] > 0 else 0.0, 3),
        "decode_ttft_p95_ms": round(
            1e3 * (disagg["ttft_p95_s"] or 0.0), 2),
        "colocated_ttft_p95_ms": round(
            1e3 * (colo["ttft_p95_s"] or 0.0), 2),
        "decode_ttft_p95_ratio": round(
            disagg["ttft_p95_s"] / colo["ttft_p95_s"], 3)
            if colo["ttft_p95_s"] else None,
    }
    log(f"disaggregated serving: {disagg['requests']} requests, "
        f"{disagg['transfers']} page transfers, "
        f"{xfer_metrics['transfer_lost_requests']} lost (gate: 0), "
        f"transfer hop {xfer_metrics['transfer_overhead_pct']}% of "
        f"active processing (gate < 10%), TTFT p95 "
        f"{xfer_metrics['decode_ttft_p95_ms']}ms disagg vs "
        f"{xfer_metrics['colocated_ttft_p95_ms']}ms colocated (ratio "
        f"{xfer_metrics['decode_ttft_p95_ratio']}, gate < 2)")
except Exception as e:
    log(f"disaggregated serving section FAILED: "
        f"{type(e).__name__}: {e}")
    xfer_metrics = {"xfer_error": f"{type(e).__name__}: {e}"[:200]}

# ------------------------------------------------------- (f) op microbench
# Per-op regression gate (reference: tools/ci_op_benchmark.sh relative
# check): ~20 hot ops + eager dispatch overhead, compared against the
# in-repo OPBENCH_BASELINE.json, which is then RE-RECORDED from this run
# (VERDICT r4 item 1a: a stale baseline defangs the gate).
from bench_ops import run_op_bench  # noqa: E402

log("op microbench (~20 ops, adaptive iters, median of 3)...")
op_results, op_vs_baseline, op_regressions, op_invalid = run_op_bench(
    SMOKE, RTT, sync_fetch, log, rerecord=not SMOKE)

# ------------------------------------------------------- (g) e2e gate
# Calibrated ratios (metric per in-run matmul TFLOP/s) vs the prior round's
# BENCH_BASELINE.json; then re-record. A slow machine scales the
# calibration and the metric together, so the RATIO is invariant to it — a
# drop beyond E2E_FACTOR is a real regression, not a slow run.
E2E_FACTOR = 1.5
E2E_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_BASELINE.json")
e2e_now = {
    "llama_train_tok_s_per_tflop": tokens_per_sec / matmul_tflops,
    "resnet50_img_s_per_tflop": resnet50_img_s / matmul_tflops,
    "resnet18_img_s_per_tflop": resnet18_img_s / matmul_tflops,
    "decode_tok_s_vs_floor": (dec_gbs / floor_gbs) if floor_gbs else None,
    "model_decode_tok_s_per_tflop": model_decode_tok_s / matmul_tflops,
}
if bert_metrics.get("bert_base_tokens_per_sec"):
    e2e_now["bert_tok_s_per_tflop"] = (
        bert_metrics["bert_base_tokens_per_sec"] / matmul_tflops)
if llama_large.get("llama_large_tokens_per_sec"):
    e2e_now["llama_large_tok_s_per_tflop"] = (
        llama_large["llama_large_tokens_per_sec"] / matmul_tflops)
if cb_metrics.get("continuous_tokens_per_sec"):
    e2e_now["continuous_tok_s_per_tflop"] = (
        cb_metrics["continuous_tokens_per_sec"] / matmul_tflops)

e2e_vs_baseline, e2e_regressions = {}, []
if os.path.exists(E2E_PATH):
    e2e_base = json.load(open(E2E_PATH)).get("metrics", {})
    for k, v in e2e_now.items():
        bv = e2e_base.get(k)
        if v and bv:
            e2e_vs_baseline[k] = round(v / bv, 3)
            if v < bv / E2E_FACTOR:
                e2e_regressions.append(k)
    if e2e_regressions:
        log(f"E2E REGRESSIONS (calibrated, >{E2E_FACTOR}x down): "
            f"{e2e_regressions}")
    else:
        log("no calibrated e2e regressions vs recorded baseline")
else:
    log(f"no e2e baseline at {E2E_PATH}"
        + ("" if SMOKE else " (recording this run)"))
if not SMOKE:
    with open(E2E_PATH, "w") as f:
        json.dump({"_meta": {"recorded_unix": int(time.time()),
                             "matmul_tflops": round(matmul_tflops, 1),
                             "device": str(kind)},
                   "metrics": {k: round(v, 4) for k, v in e2e_now.items()
                               if v}}, f, indent=1)
    log(f"re-recorded {E2E_PATH}")

result = {
    "metric": "llama_train_mfu",
    "value": round(100 * mfu, 2),
    "unit": "%",
    "vs_baseline": round(mfu / 0.50, 3),
    "tokens_per_sec": round(tokens_per_sec, 1),
    "step_ms": round(dt * 1e3, 2),
    "matmul_tflops": round(matmul_tflops, 1),
    "mfu_vs_in_run_matmul_pct": round(100 * mfu_vs_matmul, 2),
    "mfu_vs_nominal_peak_pct": round(
        100 * tokens_per_sec * flops_per_token
        / (chip_peak(kind) or peak), 2),
    **llama_large,
    "resnet50_img_per_sec": round(resnet50_img_s, 1),
    "resnet18_img_per_sec": round(resnet18_img_s, 1),
    **bert_metrics,
    "decode_tokens_per_sec": round(decode_tok_s, 1),
    "decode_cache_read_gb_s": round(dec_gbs, 1),
    "decode_us_per_step_min_med_max": [
        round(dec_sorted[0] * 1e6), round(dec_dt * 1e6),
        round(dec_sorted[-1] * 1e6)],
    "streaming_floor_gb_s": round(floor_gbs, 1),
    "decode_vs_streaming_floor": round(dec_gbs / floor_gbs, 2),
    "model_decode_tokens_per_sec": round(model_decode_tok_s, 1),
    "model_decode_ms_per_token_step": round(gen_dt / GNEW * 1e3, 2),
    **cb_metrics,
    **fleet_metrics,
    **journal_metrics,
    **tele_metrics,
    **pw_metrics,
    **ov_metrics,
    **tp_metrics,
    **kv_metrics,
    **xfer_metrics,
    "op_bench_us": op_results,
    "op_bench_vs_baseline": op_vs_baseline,
    "op_bench_regressions": op_regressions,
    "op_bench_invalid": op_invalid,
    "e2e_vs_baseline": e2e_vs_baseline,
    "e2e_regressions": e2e_regressions,
    "n_params_m": round(n_params / 1e6, 1),
    "device": kind,
    "platform": platform,
}
print(json.dumps(result), flush=True)
