"""Overlapped serving scheduler (ISSUE 5): host/device pipelining,
prefill group-width specialization, AOT warmup.

The contract under test: the pipelined scheduler (dispatch segment N+1
from segment N's device outputs while the host consumes N) is
TOKEN-IDENTICAL to the serial scheduler for fixed seeds — across mixed
prompt lengths, chunked-prefill admissions, mid-run submits, aborts, EOS
retirement, sampling, and ``serving.engine_fault`` bisection drills.
``warmup()`` AOT-compiles every declared shape so a post-warmup run
triggers ZERO XLA compilations, and a single admission's prefill runs at
group width 1, never ``max_slots`` wide.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience, telemetry
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.frontend import ServingFrontend
from paddle_tpu.models.generation import generate
from paddle_tpu.models.serving import ContinuousBatchingEngine

from _tiny_models import MODEL_KINDS, decode_assignments, sparse_latent_model


@pytest.fixture(autouse=True)
def _clean():
    resilience.reset_faults()
    resilience.reset_counters()
    telemetry.reset_telemetry()
    yield
    resilience.reset_faults()
    resilience.reset_counters()


def _model(vocab=211):
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=256, tie_word_embeddings=True)
    paddle.seed(0)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module", params=MODEL_KINDS)
def m(request):
    """What the engine serves in the tests whose subject is the scheduler
    and not LLaMA: the dense block, or sparse experts over a latent cache
    (two page pools of different shapes on one table, and a decode segment
    with an eighth output, the routing counters), one dense layer and one
    sparse."""
    if request.param == "dense":
        return _model()
    return sparse_latent_model(vocab_size=211, num_hidden_layers=2,
                               max_position_embeddings=256)


def _engine(m, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 32)
    kw.setdefault("prompt_buckets", (16, 32))
    return ContinuousBatchingEngine(m, **kw)


def _assignments():
    return telemetry.registry().snapshot()["counters"].get(
        "serving.moe_assignments_total", 0)


def _run_counted(m, eng, prompts, max_new, segment):
    """``eng.run`` and, for a model that counts, the check that the
    routing counters came out with the tokens: every token a decode
    segment handed out (all but each request's first, which its prefill
    sampled) was counted once, whatever was discarded or replayed."""
    before = _assignments()
    outs, stats = eng.run(prompts, max_new_tokens=max_new, segment=segment)
    first = sum(o is not None and len(o) > 0 for o in outs)
    assert _assignments() - before == decode_assignments(
        m, stats["useful_tokens"] - first)
    return outs, stats


def _run_both(m, prompts, max_new, segment=4, **ekw):
    """Run the same workload through the serial and pipelined schedulers
    on separate engines (same model/params) and return both results."""
    serial = _run_counted(m, _engine(m, pipeline=False, **ekw), prompts,
                          max_new, segment)
    piped = _run_counted(m, _engine(m, pipeline=True, **ekw), prompts,
                         max_new, segment)
    return serial, piped


# ------------------------------------------------------- token identity


def test_pipelined_token_identical_greedy_mixed_lengths(m):
    """Mixed short + chunked-long prompts, more requests than slots:
    pipelined output == serial output == per-request generate()."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (5, 70, 11, 3, 33, 9, 14)]  # 70/33 chunk-prefill
    (s_outs, s_stats), (p_outs, p_stats) = _run_both(m, prompts, 10)
    assert s_stats["statuses"] == p_stats["statuses"] == ["ok"] * 7
    assert not s_stats["pipelined"] and p_stats["pipelined"]
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(p_outs[i], s_outs[i],
                                      err_msg=f"request {i}")
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=10,
                     cache="paged")._value)[0, p.size:]
        np.testing.assert_array_equal(p_outs[i], want,
                                      err_msg=f"request {i} vs generate")
    assert s_stats["useful_tokens"] == p_stats["useful_tokens"] == 70


def test_pipelined_token_identical_sampling_per_request_streams(m):
    """do_sample: per-request key streams make the speculative schedule
    bit-identical to the serial one (keys are a pure function of
    (seed, rid, token index), not of dispatch order)."""
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (6, 12, 4, 9, 15)]
    kw = dict(do_sample=True, temperature=0.8, top_k=20, seed=7)
    (s_outs, s_stats), (p_outs, p_stats) = _run_both(m, prompts, 9, **kw)
    assert s_stats["statuses"] == p_stats["statuses"] == ["ok"] * 5
    for i in range(len(prompts)):
        np.testing.assert_array_equal(p_outs[i], s_outs[i],
                                      err_msg=f"request {i}")
    # and the streams really sampled (greedy run differs)
    g_outs, _ = _engine(m).run(prompts, max_new_tokens=9, segment=4)
    assert any(not np.array_equal(g_outs[i], s_outs[i])
               for i in range(len(prompts)))


def test_pipelined_eos_retirement_identical(m):
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (4, 6, 5, 8)]
    probe = np.asarray(
        generate(m, paddle.to_tensor(prompts[0][None, :]),
                 max_new_tokens=6, cache="paged")._value)[0, 4:]
    eos = int(probe[2])
    kw = dict(max_slots=2, max_len=64, prompt_buckets=(8, 16),
              eos_token_id=eos)
    (s_outs, s_stats), (p_outs, p_stats) = _run_both(m, prompts, 12, **kw)
    assert s_stats["statuses"] == p_stats["statuses"] == ["ok"] * 4
    for i in range(4):
        np.testing.assert_array_equal(p_outs[i], s_outs[i],
                                      err_msg=f"request {i}")


def test_pipelined_mid_run_submits_and_aborts_match_serial(m):
    """Stepwise session with requests arriving over time and one abort:
    completed requests are token-identical; the aborted request's partial
    tokens are a prefix of the serial scheduler's (the pipelined host
    view runs one segment behind the device)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (5, 9, 7, 12)]

    def drive(pipeline):
        eng = _engine(m, max_slots=2, pipeline=pipeline)
        eng.start(segment=4)
        r0 = eng.submit(prompts[0], 12, rid=0)
        r1 = eng.submit(prompts[1], 12, rid=1)
        eng.step()
        r2 = eng.submit(prompts[2], 12, rid=2)   # arrives mid-run
        r3 = eng.submit(prompts[3], 30, rid=3)
        eng.step()
        eng.abort(3)                              # cancelled mid-run
        while eng.has_work():
            eng.step()
        return [r0, r1, r2, r3]

    serial = drive(False)
    piped = drive(True)
    for i in (0, 1, 2):
        assert serial[i].status == piped[i].status == "ok"
        np.testing.assert_array_equal(piped[i].output(), serial[i].output(),
                                      err_msg=f"request {i}")
    assert serial[3].status == piped[3].status == "cancelled"
    st, pt = serial[3].output(), piped[3].output()
    np.testing.assert_array_equal(pt, st[:len(pt)])


def test_pipelined_engine_fault_bisection_identical(m):
    """The sticky-poison drill on the pipelined path: same offender, same
    survivor tokens as the serial scheduler (bisection drains the
    pipeline before replaying)."""
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (5, 11, 3)]
    set_flags({"FLAGS_fault_injection": "serving.engine_fault:1"})
    s_outs, s_stats = _run_counted(m, _engine(m, pipeline=False), prompts,
                                   10, 4)
    resilience.reset_faults()
    set_flags({"FLAGS_fault_injection": "serving.engine_fault:1"})
    p_outs, p_stats = _run_counted(m, _engine(m), prompts, 10, 4)
    assert s_stats["statuses"] == p_stats["statuses"] == \
        ["failed", "ok", "ok"]
    for i in (1, 2):
        np.testing.assert_array_equal(p_outs[i], s_outs[i],
                                      err_msg=f"request {i}")
    assert resilience.get_counter("serving.poison_request") == 2  # both runs


def test_pipelined_segment_dispatch_failure_bisects_after_drain(m):
    """A decode-segment dispatch failure mid-pipeline drains the in-flight
    segment, then bisects the active mask — offender alone retires
    ``failed``, peers finish with exact greedy tokens."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (5, 7, 9)]
    eng = _engine(m)
    assert eng.start()._pipeline  # the default: pipelined
    orig = eng._segment_p

    def boom(params, ks, vs, tables, lengths, toks, active, limits, keys):
        if bool(np.asarray(active)[1]):
            raise RuntimeError("simulated XLA dispatch failure")
        return orig(params, ks, vs, tables, lengths, toks, active, limits,
                    keys)

    eng._segment_p = boom
    outs, stats = _run_counted(m, eng, prompts, 6, 2)
    assert stats["statuses"] == ["ok", "failed", "ok"]
    for i in (0, 2):
        want = np.asarray(
            generate(m, paddle.to_tensor(prompts[i][None, :]),
                     max_new_tokens=6, cache="paged")._value
        )[0, prompts[i].size:]
        np.testing.assert_array_equal(outs[i], want, err_msg=f"request {i}")
    assert resilience.get_counter("serving.poison_request") == 1


def test_pipelined_async_consume_failure_replays_serially(m):
    """A segment whose ASYNC execution fails (the error surfaces at the
    output fetch, not at dispatch) must not escape ``step()``: the
    speculative successor is discarded and the window replays serially
    from the last synced host state — requests still finish ``ok`` with
    exact greedy tokens."""
    rng = np.random.RandomState(12)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32) for n in (5, 9)]
    eng = _engine(m, max_slots=2)
    orig = eng._segment_p
    calls = {"n": 0}

    class _Poison:  # np.asarray inside jax.device_get trips this
        def __array__(self, *a, **k):
            raise RuntimeError("simulated async execution failure")

    def flaky(*args):
        out = orig(*args)
        calls["n"] += 1
        if calls["n"] == 1:  # first segment: outputs poisoned at fetch
            return (_Poison(),) + tuple(out[1:])
        return out

    eng._segment_p = flaky
    outs, stats = _run_counted(m, eng, prompts, 8, 3)
    assert stats["statuses"] == ["ok", "ok"]
    assert stats["failed"] == 0          # replay, not retirement
    for i, p in enumerate(prompts):
        want = np.asarray(
            generate(m, paddle.to_tensor(p[None, :]), max_new_tokens=8,
                     cache="paged")._value)[0, p.size:]
        np.testing.assert_array_equal(outs[i], want, err_msg=f"request {i}")


def test_serial_fallback_argument_selects_serial_loop():
    m = _model()
    eng = _engine(m, pipeline=False)
    assert not eng.start()._pipeline
    assert not eng.stats()["pipelined"]
    assert _engine(m).start()._pipeline   # the default: overlapped


# ------------------------------------------------------- program names


@pytest.mark.parametrize("attr,name", [
    ("_prefill_p", "prefill"), ("_chunk_p", "chunk_step"),
    ("_final_chunk_p", "final_chunk"), ("_resume_p", "resume_final"),
    ("_segment_p", "segment")])
def test_decode_path_programs_keep_the_names_the_benchmark_finds(attr, name):
    """A device trace shows a program as ``jit_<name>``, and that is how
    the benchmark's readers find it (``benchmark/metrics/*.json``
    ``"program": "^jit_segment"``; ``prefill_dev_us_per_tok`` sums
    ``jit_prefill``, ``jit_chunk_step``, ``jit_final_chunk``,
    ``jit_resume_final``): a rename turns those metrics null with no
    other test failing."""
    assert getattr(_engine(_model()), attr).__name__ == name


# ------------------------------------------- prefill width specialization


def test_single_admission_prefill_is_not_max_slots_wide():
    """Group-width specialization: a single admission's prefill batch is
    width 1 (asserted via the traced prompts shape), and widths grow as
    the next power of two of the group size, capped at max_slots."""
    m = _model()
    eng = _engine(m, max_slots=3)
    widths = []
    orig = eng._prefill_p

    def spy(params, ks, vs, prompts, rows, lens, keys):
        widths.append(prompts.shape[0])
        return orig(params, ks, vs, prompts, rows, lens, keys)

    eng._prefill_p = spy
    rng = np.random.RandomState(6)
    p = lambda n: rng.randint(0, 211, (n,)).astype(np.int32)
    eng.run([p(9)], max_new_tokens=3, segment=2)
    assert widths == [1]                  # single admission: width 1
    widths.clear()
    eng.run([p(9), p(11)], max_new_tokens=3, segment=2)
    assert widths == [2]
    widths.clear()
    eng.run([p(9), p(11), p(8)], max_new_tokens=3, segment=2)
    assert widths == [3]                  # pow2 would be 4: capped at slots
    assert eng.group_widths() == (1, 2, 3)


def test_chunked_prefill_width_specialized():
    m = _model()
    eng = _engine(m, max_slots=2)
    widths = []
    orig = eng._chunk_p
    eng._chunk_p = lambda *a: (widths.append(a[3].shape[0]), orig(*a))[1]
    rng = np.random.RandomState(7)
    long_p = rng.randint(0, 211, (70,)).astype(np.int32)
    eng.run([long_p], max_new_tokens=3, segment=2)
    assert widths and all(w == 1 for w in widths)


# ----------------------------------------------------------- AOT warmup


def test_warmup_precompiles_every_shape_zero_compiles_after(m):
    """After ``warmup()``, a full run (mixed buckets, chunked prefill,
    every admission width, decode segments) triggers ZERO XLA backend
    compilations — measured with the shared jit-layer compile listener
    (``count_backend_compiles``, the production watchdog's test form)."""
    from paddle_tpu.jit import count_backend_compiles

    eng = _engine(m, max_slots=2, max_len=64, prompt_buckets=(8, 16))
    info = eng.warmup(segment=3)
    # 2 widths x 2 buckets x (prefill + prefix-resume) + 2 widths x
    # (chunk + final) + the one segment program + the CoW page-copy
    # program + the KV export/import chunk programs (page-transfer data
    # plane)
    assert info["programs"] == 2 * 2 * 2 + 2 * 2 + 1 + 1 + 2
    again = eng.warmup(segment=3)          # idempotent: everything cached
    assert again["programs"] == 0 and again["cached"] == 16
    with count_backend_compiles() as compiles:
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
                   for n in (5, 30, 12, 7, 20)]  # 30/20: chunked (>16)
        outs, stats = eng.run(prompts, max_new_tokens=6, segment=3)
    assert stats["statuses"] == ["ok"] * 5
    assert compiles == [], f"post-warmup run compiled {len(compiles)} programs"


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_dir", "checkout_dir"])
def test_compilation_cache_is_placed_from_outside(tmp_path, monkeypatch,
                                                  from_env):
    """``jit.enable_compilation_cache()`` takes no directory: with
    ``JAX_COMPILATION_CACHE_DIR`` set the cache is THAT directory and the
    program sets no other; unset, it is the one fixed path at the root
    of the checkout."""
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from paddle_tpu import jit as pjit

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if from_env:
        want = str(tmp_path / "jaxcache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        want = os.path.join(repo, ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    dirs_set = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            dirs_set.append(value)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    try:
        assert pjit.enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        if from_env:
            # the warmup compiles really land on disk there (jax latches
            # cache initialization at first compile; the helper resets
            # it or the directory would be ignored)
            eng = _engine(_model(), max_slots=2, max_len=32,
                          prompt_buckets=(8,))
            info = eng.warmup(segment=2)
            assert info["programs"] >= 3  # 2 widths x 1 bucket + segment
            assert os.path.isdir(want) and len(os.listdir(want)) > 0
        assert dirs_set == [want]  # set once, to no other directory
    finally:
        monkeypatch.undo()
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_warmed_engine_matches_unwarmed_tokens(m):
    """AOT executables are the SAME programs: warmed and unwarmed engines
    emit identical tokens (greedy and sampled)."""
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, 211, (n,)).astype(np.int32)
               for n in (5, 40, 11)]
    for kw in (dict(), dict(do_sample=True, temperature=0.9, seed=3)):
        cold_outs, _ = _engine(m, **kw).run(prompts, max_new_tokens=7,
                                            segment=3)
        warm_eng = _engine(m, **kw)
        warm_eng.warmup(segment=3)
        warm_outs, _ = warm_eng.run(prompts, max_new_tokens=7, segment=3)
        for i in range(len(prompts)):
            np.testing.assert_array_equal(warm_outs[i], cold_outs[i],
                                          err_msg=f"request {i} {kw}")


# ------------------------------------------------------------ observability


def test_host_gap_stat_and_pipeline_marker():
    m = _model()
    eng = _engine(m)
    rng = np.random.RandomState(10)
    prompts = [rng.randint(0, 211, (6,)).astype(np.int32) for _ in range(3)]
    _, stats = eng.run(prompts, max_new_tokens=8, segment=2)
    assert stats["host_gap_ms"] >= 0.0
    assert stats["pipelined"] is True
    assert "host_gap_ms" in ContinuousBatchingEngine.stats.__doc__
    assert "warmup" in ContinuousBatchingEngine.stats.__doc__


# ------------------------------------------------------- frontend threading


def test_frontend_over_pipelined_engine_with_warmup(m):
    """The full stack: warmed engine + frontend lifecycle (submit over
    time, cancel, drain) over the pipelined scheduler — results identical
    to per-request generate()."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 211, (6,)).astype(np.int32) for _ in range(4)]
    eng = _engine(m, max_slots=2)
    fe = ServingFrontend(eng, max_queue=8, segment=3)
    fe.warmup()
    rids = [fe.submit(p, max_new_tokens=8) for p in prompts[:2]]
    fe.step()
    rids.append(fe.submit(prompts[2], max_new_tokens=8))
    c = fe.submit(prompts[3], max_new_tokens=8)
    assert fe.cancel(c)
    res = fe.results(wait=True)
    for i, rid in enumerate(rids):
        assert res[rid].status == "ok"
        want = np.asarray(
            generate(m, paddle.to_tensor(prompts[i][None, :]),
                     max_new_tokens=8, cache="paged")._value
        )[0, prompts[i].size:]
        np.testing.assert_array_equal(res[rid].tokens, want,
                                      err_msg=f"request {i}")
    assert res[c].status == "cancelled"
    fe.shutdown(drain=True)
    assert not eng.has_work()
