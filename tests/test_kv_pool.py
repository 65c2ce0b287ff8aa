"""Dynamic paged-KV allocator + copy-on-write prefix caching (ISSUE 14).

The engine's slot->page map is a free-list :class:`kv_pool.PagePool`:
pages are granted at admission, appended as decode crosses page
boundaries, freed at retirement; admission is bounded by available
pages (``serving.kv_pool_exhausted`` deferral) and a running decode is
never failed — under pool pressure the youngest slot is PREEMPTED back
to the queue and resumes bit-identically. Prompt prefixes are shared
copy-on-write through a content-verified chained-hash
:class:`kv_pool.PrefixCache`.

Load-bearing invariants drilled here:

* token streams BIT-IDENTICAL to the unshared engine — greedy and
  sampled, serial and pipelined, across CoW mid-page divergence,
  chunked-prefill resume, pool-exhausted deferral, and preemption;
* refcounts keep shared pages alive across the owners' retirements;
* ZERO post-warmup XLA compiles through the allocator/prefix path;
* ``serving.engine_fault`` bisection still isolates poison requests
  and leaks no pages;
* the PR 12 TP engine serves sharded dynamic pools bit-identically.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience, telemetry
from paddle_tpu.core.flags import set_flags
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.frontend import ServingFrontend
from paddle_tpu.models.kv_pool import PagePool, PrefixCache
from paddle_tpu.models.serving import ContinuousBatchingEngine

from _tiny_models import MODEL_KINDS, sparse_latent_model


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    resilience.reset_faults()
    resilience.reset_counters()
    telemetry.reset_telemetry()
    set_flags({"FLAGS_flight_dir": str(tmp_path / "flight")})
    yield
    resilience.reset_faults()
    resilience.reset_counters()
    telemetry.reset_telemetry()
    set_flags({"FLAGS_flight_dir": ""})


_CFG = LlamaConfig(vocab_size=151, hidden_size=32, intermediate_size=64,
                   num_hidden_layers=2, num_attention_heads=4,
                   max_position_embeddings=512, tie_word_embeddings=True)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(_CFG)


@pytest.fixture(scope="module", params=MODEL_KINDS)
def served(request, model):
    """What the allocator's engine-level drills serve: the dense block,
    or sparse experts over a latent cache (one dense layer, one sparse),
    whose two page pools have different shapes on the one table the
    allocator grants from."""
    if request.param == "dense":
        return model
    return sparse_latent_model(vocab_size=_CFG.vocab_size,
                               num_hidden_layers=2,
                               max_position_embeddings=512)


def _engine(model, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 32)
    kw.setdefault("prompt_buckets", (16, 32, 64))
    kw.setdefault("seed", 7)
    return ContinuousBatchingEngine(model, **kw)


def _rng(seed=1):
    return np.random.RandomState(seed)


def _toks(rng, n):
    return rng.randint(0, 151, (n,)).astype(np.int32)


def _serve(eng, subs, segment=4, serialize_first=True):
    """Submit ``subs`` = [(rid, prompt, max_new)] and run to completion.
    ``serialize_first`` drains the first request before submitting the
    rest, so its prompt pages are cached when the others admit."""
    eng.start(segment=segment)
    reqs = []
    for i, (rid, p, new) in enumerate(subs):
        reqs.append(eng.submit(p, new, rid=rid))
        if i == 0 and serialize_first:
            while eng.has_work():
                eng.step()
    while eng.has_work():
        eng.step()
    return [np.asarray(r.tokens, np.int32) for r in reqs], reqs


# --------------------------------------------------------------- units


def test_page_pool_alloc_refcount_recycle():
    freed = []
    pool = PagePool(4)
    assert pool.available() == 4
    got = pool.alloc(3)
    assert len(got) == 3 and pool.available() == 1
    assert pool.alloc(2) is None          # short: caller defers
    pool.incref(got[0])                   # shared mapping
    dead = pool.decref(got)
    assert dead == got[1:]                # got[0] still referenced
    pool.recycle(dead)
    assert pool.available() == 3
    assert pool.decref([got[0]]) == [got[0]]
    freed.append(pool.refcount(got[0]))
    assert freed == [0]


def test_prefix_cache_match_insert_evict_verifies_tokens():
    pool = PagePool(8)
    recycled = []
    cache = PrefixCache(pool, 4, recycled.extend)
    long_p = np.arange(16, dtype=np.int32)          # 4 full pages
    pages = pool.alloc(4)
    cache.insert(long_p, pages)
    assert len(cache) == 4
    # a SHORTER prompt inside the cached prefix: full pages match, the
    # mid-page tail partial-matches the next cached page (CoW material)
    short = long_p[:11]
    hit, matched, partial = cache.match(short)
    assert hit == pages[:2] and matched == 8
    assert partial is not None and partial.r == 3
    assert partial.page == pages[2]
    # content is VERIFIED: a hash chain can never alias foreign tokens
    other = long_p[:11].copy()
    other[2] = 99
    hit2, matched2, partial2 = cache.match(other)
    assert hit2 == [] and matched2 == 0
    assert partial2 is not None and partial2.r == 2  # head of page 0
    # eviction is leaf-first LRU and only frees unreferenced pages
    pool.recycle(pool.decref(pages))                # slot lets go
    freed = cache.evict(10)
    assert freed == 4 and len(cache) == 0
    assert sorted(recycled) == sorted(pages)


# -------------------------------------------- bit-exactness invariants


@pytest.mark.parametrize("pipeline", [False, True])
@pytest.mark.parametrize("do_sample", [False, True])
def test_shared_prefix_streams_bit_identical(served, pipeline, do_sample):
    """Prefix-shared streams == unshared streams, greedy + per-request
    key-stream sampling, serial + pipelined — including a full-page hit,
    a mid-page CoW divergence, and an identical-prompt replay."""
    rng = _rng(2)
    pre = _toks(rng, 48)                     # 1.5 pages of 32
    subs = [(1, np.concatenate([pre, _toks(rng, 20)]), 10),
            (2, np.concatenate([pre, _toks(rng, 9)]), 10),   # page hit
            (3, pre[:40].copy(), 10),        # inside req 1, ends MID-PAGE
            (4, np.concatenate([pre, _toks(rng, 20)]), 10)]
    kw = dict(pipeline=pipeline, do_sample=do_sample, top_k=8)
    got, _ = _serve(_engine(served, prefix_cache=True, **kw), subs)
    want, _ = _serve(_engine(served, prefix_cache=False, **kw), subs)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")


def test_cow_divergence_leaves_the_owner_intact(served):
    """A mid-page CoW admission while the prefix OWNER is still decoding:
    both streams match their unshared references (the copy really is a
    copy — the writer never touches the shared page)."""
    rng = _rng(3)
    pre = _toks(rng, 64)                     # 2 pages
    p_owner = np.concatenate([pre, _toks(rng, 8)])
    p_cow = pre[:50].copy()                  # diverges mid page 1
    for pc in (True, False):
        eng = _engine(served, prefix_cache=pc)
        eng.start(segment=2)
        owner = eng.submit(p_owner, 24, rid=1)
        eng.step()                           # owner admitted + decoding
        cow = eng.submit(p_cow, 24, rid=2)   # maps owner's pages
        while eng.has_work():
            eng.step()
        if pc:
            got = (np.asarray(owner.tokens), np.asarray(cow.tokens))
            assert eng.kv_stats()["prefix_tokens_saved"] > 0
        else:
            want = (np.asarray(owner.tokens), np.asarray(cow.tokens))
    np.testing.assert_array_equal(got[0], want[0], err_msg="owner")
    np.testing.assert_array_equal(got[1], want[1], err_msg="cow reader")


def test_refcount_survives_owner_retirement(served):
    """Shared pages outlive the request that computed them: a later
    identical-prefix request hits the cache after the owner retired, and
    the pages only return to the pool once the cache lets go."""
    rng = _rng(4)
    pre = _toks(rng, 64)
    eng = _engine(served)
    subs = [(1, np.concatenate([pre, _toks(rng, 12)]), 8),
            (2, np.concatenate([pre, _toks(rng, 5)]), 8)]
    _, reqs = _serve(eng, subs)              # serialized: 1 retires first
    assert all(r.status == "ok" for r in reqs)
    kv = eng.kv_stats()
    assert kv["prefix_tokens_saved"] >= 64   # req 2 skipped the prefix
    assert kv["prefix_cached_pages"] > 0
    # every non-cache reference was released at retirement
    assert (kv["pages_free"] + kv["prefix_cached_pages"]
            == kv["pages_total"])


def test_chunked_prefill_resume_long_prompts(served):
    """Prompts beyond the largest bucket resume their chunked prefill at
    the first divergent page (page-aligned) — streams identical to the
    cold engine's."""
    rng = _rng(5)
    shared = _toks(rng, 96)
    subs = [(1, np.concatenate([shared, _toks(rng, 70)]), 8),
            (2, np.concatenate([shared, _toks(rng, 81)]), 8)]
    kw = dict(max_len=256, max_slots=2, prompt_buckets=(16, 64))
    got, _ = _serve(_engine(served, prefix_cache=True, **kw), subs)
    want, _ = _serve(_engine(served, prefix_cache=False, **kw), subs)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")


# ------------------------------------------------ pool-pressure drills


def test_pool_exhausted_defers_admission_never_fails(served):
    """A pool sized well below max_slots * per_seq: admissions defer
    with ``serving.kv_pool_exhausted`` backpressure, every request still
    finishes ok, and the streams match the uncontended engine's."""
    rng = _rng(6)
    prompts = [_toks(rng, 10) for _ in range(6)]
    subs = [(i, p, 40) for i, p in enumerate(prompts)]
    tight = _engine(served, max_slots=6, prompt_buckets=(16,),
                    pool_pages=6)
    got, reqs = _serve(tight, subs, serialize_first=False)
    assert all(r.status == "ok" for r in reqs)
    assert resilience.counters().get("serving.kv_pool_exhausted", 0) > 0
    roomy = _engine(served, max_slots=6, prompt_buckets=(16,))
    want, _ = _serve(roomy, subs, serialize_first=False)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")
    # retirement returned every grant
    kv = tight.kv_stats()
    assert kv["pages_free"] + kv["prefix_cached_pages"] \
        == kv["pages_total"]


def test_preemption_resumes_bit_identically(served):
    """Decode growth outrunning the pool preempts the youngest slot
    (``serving.kv_preempted``) instead of failing it; the preempted
    request re-admits through the prefix cache and its final stream is
    bit-identical to the uncontended run."""
    rng = _rng(7)
    # short prompts, long decode: admission fits but growth collides
    prompts = [_toks(rng, 6) for _ in range(4)]
    subs = [(i, p, 60) for i, p in enumerate(prompts)]
    tight = _engine(served, max_slots=4, max_len=96, prompt_buckets=(8,),
                    pool_pages=5)
    got, reqs = _serve(tight, subs, serialize_first=False)
    assert all(r.status == "ok" for r in reqs)
    assert resilience.counters().get("serving.kv_preempted", 0) > 0
    roomy = _engine(served, max_slots=4, max_len=96, prompt_buckets=(8,))
    want, _ = _serve(roomy, subs, serialize_first=False)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")


def test_preempted_fold_past_chunk_width_stays_compiled(served):
    """A preempted request whose folded prompt (orig + emitted) outgrows
    the largest bucket re-admits through the CHUNKED path even on an
    engine whose max_len is NOT a chunk multiple (submit() rejects such
    long prompts, but preemption creates them legitimately): the chunk
    programs must be in the warmed set — zero post-warmup compiles —
    and the streams stay bit-identical to the uncontended run."""
    from paddle_tpu.jit import count_backend_compiles

    rng = _rng(15)
    # max_len 24 is NOT a multiple of chunk_w 16; 10-token prompts with
    # max_new 10 on a 4-page pool admit together under the serial
    # scheduler's one-segment headroom, then COLLIDE on growth — the
    # preempted one folds to a 17+-token prompt
    kw = dict(max_slots=2, max_len=24, page_size=8, prompt_buckets=(16,),
              prefix_cache=False, pipeline=False)
    prompts = [_toks(rng, 10) for _ in range(2)]
    subs = [(i, p, 10) for i, p in enumerate(prompts)]
    tight = _engine(served, pool_pages=4, **kw)
    tight.warmup(segment=4)
    with count_backend_compiles() as compiles:
        got, reqs = _serve(tight, subs, serialize_first=False)
    assert all(r.status == "ok" for r in reqs)
    assert resilience.counters().get("serving.kv_preempted", 0) > 0
    assert compiles == [], \
        f"preempted-fold path compiled {len(compiles)} programs"
    want, _ = _serve(_engine(served, **kw), subs, serialize_first=False)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")


def test_kv_bytes_count_shared_pages_once(served):
    """Physical byte accounting under prefix sharing: slots mapping the
    same cached pages must not report more bytes in use than the pool
    physically holds (grants stay the fragmentation denominator)."""
    rng = _rng(16)
    pre = _toks(rng, 64)                  # 2 shared pages of 32
    eng = _engine(served)
    eng.start(segment=2)
    reqs = [eng.submit(np.concatenate([pre, _toks(rng, 4)]), 30, rid=r)
            for r in (1, 2, 3)]
    eng.step()                            # rid 1 admits, pages cached
    for _ in range(3):
        eng.step()                        # rids 2-3 share the prefix
    kv = eng.kv_stats()
    pool_bytes = (kv["pages_total"] * eng.page_size
                  * kv["bytes_per_token"])
    assert 0 < kv["bytes_in_use"] <= pool_bytes, kv
    assert kv["pages_granted"] <= kv["pages_total"]
    assert 0.0 <= kv["fragmentation_pct"] <= 100.0
    for r in reqs:
        eng.abort(r.rid)
    while eng.has_work():
        eng.step()


def test_engine_fault_bisection_over_dynamic_allocator(served):
    """The PR 3 poison-isolation contract holds on the dynamic pool: the
    poisoned request fails alone, its co-batched peers finish with exact
    tokens, and no page leaks (everything not cache-held returns)."""
    rng = _rng(8)
    prompts = [_toks(rng, 12) for _ in range(4)]
    subs = [(i, p, 8) for i, p in enumerate(prompts)]
    want, _ = _serve(_engine(served), subs, serialize_first=False)
    set_flags({"FLAGS_fault_injection": "serving.engine_fault:1"})
    eng = _engine(served)
    got, reqs = _serve(eng, subs, serialize_first=False)
    statuses = [r.status for r in reqs]
    assert statuses.count("failed") == 1
    assert resilience.counters().get("serving.poison_request", 0) == 1
    for i, r in enumerate(reqs):
        if r.status == "ok":
            np.testing.assert_array_equal(
                np.asarray(r.tokens), want[i], err_msg=f"survivor {i}")
    kv = eng.kv_stats()
    assert kv["pages_free"] + kv["prefix_cached_pages"] \
        == kv["pages_total"]


# --------------------------------------------- compile & config hygiene


def test_zero_compiles_through_allocator_and_prefix_path(served):
    """A warmed engine records ZERO XLA compiles while serving through
    dynamic grants, CoW copies, prefix-resume prefill, and decode growth
    — page-table CONTENTS change, traced shapes don't."""
    from paddle_tpu.jit import count_backend_compiles

    rng = _rng(9)
    pre = _toks(rng, 48)
    eng = _engine(served, max_slots=2, max_len=64,
                  prompt_buckets=(8, 16), page_size=16)
    eng.warmup(segment=3)
    with count_backend_compiles() as compiles:
        subs = [(1, np.concatenate([pre[:16], _toks(rng, 5)]), 6),
                (2, np.concatenate([pre[:16], _toks(rng, 3)]), 6),
                (3, pre[:27].copy(), 6)]     # mid-page CoW
        _, reqs = _serve(eng, subs, segment=3)
    assert all(r.status == "ok" for r in reqs)
    assert eng.kv_stats()["prefix_tokens_saved"] > 0
    assert compiles == [], \
        f"allocator path compiled {len(compiles)} programs"


def test_max_len_round_up_is_surfaced(model):
    """Satellite: the silent page-multiple round-up of ``max_len`` is
    logged and surfaced in ``stats()['kv']``."""
    eng = _engine(model, max_len=100, page_size=32)   # -> 128
    assert eng.max_len == 128
    eng.start(segment=2)
    kv = eng.stats()["kv"]
    assert kv["max_len"] == 128
    assert kv["max_len_rounded_from"] == 100
    clean = _engine(model, max_len=128, page_size=32)
    clean.start(segment=2)
    assert clean.stats()["kv"]["max_len_rounded_from"] is None


def test_pool_must_hold_one_full_sequence(model):
    with pytest.raises(ValueError, match="pool_pages"):
        _engine(model, pool_pages=2)          # < per_seq (128/32 = 4)


# ------------------------------------------------- gauges & frontend


def test_kv_pool_gauges_and_frontend_health(model):
    """The redefined gauges (`serving.kv_pages_free` /
    `serving.kv_pages_total` / `serving.kv_fragmentation_pct` over
    granted pages, `serving.prefix_hit_rate`, per-slot
    `serving.kv_slot_pages{slot=}`) land in the registry, and the
    frontend surfaces pool pressure in ``health()``."""
    rng = _rng(10)
    pre = _toks(rng, 32)
    eng = _engine(model)
    fe = ServingFrontend(eng, max_queue=8, segment=2)
    r1 = fe.submit(np.concatenate([pre, _toks(rng, 6)]),
                   max_new_tokens=12)
    fe.step()
    r2 = fe.submit(np.concatenate([pre, _toks(rng, 4)]),
                   max_new_tokens=12)
    fe.step()
    h = fe.health()
    assert h["kv_pages_total"] == eng.kv_stats()["pages_total"]
    assert 0 <= h["kv_pages_free"] <= h["kv_pages_total"]
    assert "kv_fragmentation_pct" in h and "prefix_hit_rate" in h
    assert h["kv_admission_blocked"] is False
    snap = telemetry.registry().snapshot()
    g = snap["gauges"]
    assert g["serving.kv_pages_total"] == eng.kv_stats()["pages_total"]
    assert "serving.kv_pages_free" in g
    assert "serving.kv_fragmentation_pct" in g
    assert "serving.prefix_hit_rate" in g
    assert any(k.startswith("serving.kv_slot_pages{")
               for k in g), list(g)
    # the second submit shared the first's prefix: the saved-token
    # counter (serving.prefix_tokens_saved) ticked
    assert snap["counters"].get("serving.prefix_tokens_saved", 0) > 0
    res = fe.results(wait=True, timeout=60)
    assert res[r1].status == "ok" and res[r2].status == "ok"
    fe.shutdown(drain=True)


def test_frontend_holds_queue_on_pool_backpressure(model):
    """When the engine defers its queue head on pool exhaustion, the
    frontend stops spilling entries into the engine's FIFO — they wait
    in the frontend's priority queue (`kv_admission_blocked`)."""
    rng = _rng(11)
    eng = _engine(model, max_slots=6, prompt_buckets=(16,), pool_pages=4)
    fe = ServingFrontend(eng, max_queue=16, segment=2)
    rids = [fe.submit(_toks(rng, 12), max_new_tokens=48)
            for _ in range(4)]
    saw_blocked = False
    for _ in range(60):
        fe.step()
        if fe.health()["kv_admission_blocked"]:
            saw_blocked = True
            break
    assert saw_blocked
    # new submits while blocked wait in the FRONTEND's priority queue,
    # not the engine's FIFO
    engine_queued = len(eng.queued_requests())
    late = [fe.submit(_toks(rng, 12), max_new_tokens=48)
            for _ in range(2)]
    fe.step()
    if fe.health()["kv_admission_blocked"]:
        assert len(eng.queued_requests()) <= engine_queued
    res = fe.results(wait=True, timeout=120)
    assert sorted(res) == sorted(rids + late)
    assert all(r.status == "ok" for r in res.values())
    fe.shutdown(drain=True)


def test_obs_kv_renders_live_and_snapshot(model, tmp_path, capsys):
    """`obs kv` renders pool occupancy, fragmentation, prefix hit rate,
    and per-slot page counts from the live registry and from a saved
    snapshot (the `obs slo`/`obs fleet` pattern)."""
    import json

    from paddle_tpu.tools.obs import main as obs_main

    rng = _rng(12)
    pre = _toks(rng, 32)
    eng = _engine(model)
    subs = [(1, np.concatenate([pre, _toks(rng, 5)]), 6),
            (2, np.concatenate([pre, _toks(rng, 3)]), 6)]
    _serve(eng, subs)
    assert obs_main(["kv"]) == 0
    out = capsys.readouterr().out
    assert "pages granted" in out and "prefix" in out
    snap_path = tmp_path / "snap.json"
    snap_path.write_text(json.dumps(telemetry.registry().snapshot()))
    assert obs_main(["kv", str(snap_path)]) == 0
    out = capsys.readouterr().out
    assert "per-slot granted pages" in out
    assert obs_main(["kv", str(tmp_path / "nope.json")]) == 2


def test_kv_pool_summary_from_snapshot(model):
    rng = _rng(13)
    from paddle_tpu.core import perfwatch

    eng = _engine(model)
    _serve(eng, [(1, _toks(rng, 20), 6)])
    s = perfwatch.kv_pool_summary()
    assert s["pages_total"] == eng.kv_stats()["pages_total"]
    s2 = perfwatch.kv_pool_summary(telemetry.registry().snapshot())
    assert s2["pages_total"] == s["pages_total"]
    assert isinstance(s2["slot_pages"], dict)


# ------------------------------------------------------------ TP pools


def test_tp_sharded_dynamic_pool_bit_identity(model):
    """PR 12 contract over the dynamic allocator: a TP engine (degree 1
    mesh — degree > 1 needs multiple devices) over a page pool with
    prefix sharing emits streams bit-identical to the single-chip
    engine."""
    from paddle_tpu.models.tp_serving import TPShardedEngine, serving_mesh

    rng = _rng(14)
    pre = _toks(rng, 32)
    subs = [(1, np.concatenate([pre, _toks(rng, 8)]), 8),
            (2, np.concatenate([pre, _toks(rng, 5)]), 8)]
    mesh = serving_mesh(1)
    tp = TPShardedEngine(model, max_slots=4, max_len=128, page_size=32,
                         prompt_buckets=(16, 32, 64), seed=7, mesh=mesh,
                         pool_pages=12)
    got, reqs = _serve(tp, subs)
    assert all(r.status == "ok" for r in reqs)
    assert tp.kv_stats()["prefix_tokens_saved"] > 0
    want, _ = _serve(_engine(model), subs)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")
