"""Tensor-parallel serving replicas (ISSUE 12).

Three layers:

* ENGINE: ``TPShardedEngine`` lays params + paged KV pools over a
  ProcessMesh; token streams must be BIT-IDENTICAL to the single-chip
  engine (the fleet failover contract — a TP group and a single-chip
  replica are interchangeable), and a warmed TP engine must record zero
  post-warmup XLA compiles, now per mesh.
* MEMBERSHIP: ``TPGroupMembership`` rides the gang machinery — a member
  death (or the ``tp.member_death`` / ``tp.collective_timeout`` drill
  sites) surfaces as ``PeerFailureError`` within one lease; the group
  fails as ONE unit, so the router charges one death, not N.
* FLEET: the flagship multi-process drill (slow) — ``launch_fleet``
  with one TP-gang replica (2 member processes) + one single-chip
  replica under live traffic; SIGKILL a gang MEMBER mid-decode → the
  whole group dies within one lease, the router trips the group's
  breaker, zero requests are lost, every failover stream is
  bit-identical to the uninterrupted run, and the respawned gang
  re-forms and serves again.
"""
import json
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience, telemetry
from paddle_tpu.core.flags import set_flags
from paddle_tpu.core.resilience import PeerFailureError
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.jit.compile_watch import compile_watchdog, count_backend_compiles
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.frontend import ServingFrontend
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.models.router import ServingRouter
from paddle_tpu.models.tp_serving import (
    TPGroupMembership,
    TPShardedEngine,
    plan_tp_shardings,
    serving_mesh,
    tp_member_main,
)


@pytest.fixture(autouse=True)
def _clean(tmp_path):
    resilience.reset_faults()
    telemetry.reset_telemetry()
    compile_watchdog().reset()
    set_flags({"FLAGS_flight_dir": str(tmp_path / "flight")})
    yield
    resilience.reset_faults()
    telemetry.reset_telemetry()
    compile_watchdog().reset()
    set_flags({"FLAGS_flight_dir": ""})


_CFG = LlamaConfig(vocab_size=97, hidden_size=16, intermediate_size=32,
                   num_hidden_layers=2, num_attention_heads=2,
                   max_position_embeddings=128, tie_word_embeddings=True)


def _model():
    paddle.seed(0)
    return LlamaForCausalLM(_CFG)


_ENG_KW = dict(max_slots=2, max_len=64, prompt_buckets=(8, 16),
               do_sample=True, temperature=0.9, seed=13)


def _prompts(n, rng_seed=3, lo=4, hi=10):
    rng = np.random.RandomState(rng_seed)
    return [rng.randint(0, _CFG.vocab_size,
                        (int(rng.randint(lo, hi)),)).astype(np.int32)
            for _ in range(n)]


def _single_chip_reference(prompts, rids, max_new):
    """Uninterrupted single-chip run with the SAME rids — the oracle
    both the TP engine and every failover stream must match exactly.
    ``max_new`` may be a scalar or a per-rid sequence."""
    if np.isscalar(max_new):
        max_new = [max_new] * len(rids)
    fe = ServingFrontend(ContinuousBatchingEngine(_model(), **_ENG_KW),
                         max_queue=256, segment=4, breaker_threshold=50)
    for rid, p, mn in zip(rids, prompts, max_new):
        fe.submit(p, max_new_tokens=mn, rid=rid)
    out = fe.results(wait=True)
    fe.shutdown()
    return {rid: out[rid].tokens for rid in rids}


# ------------------------------------------------------------ the engine


def test_plan_shards_output_dims_only():
    """The sharding plan is the bitwise-safe subset of the Megatron
    assignment: vocab-ish params shard dim 0, projections shard their
    OUTPUT dim, nothing shards a contraction, indivisible dims stay
    replicated."""
    model = _model()
    mesh = serving_mesh(2)
    plan = plan_tp_shardings(model, mesh)
    names = dict(model.named_parameters())
    assert set(plan) == set(names)
    for name, placements in plan.items():
        shape = tuple(names[name].shape)
        shard_dims = [p.get_dim() for p in placements if p.is_shard()]
        if len(shape) != 2:
            assert not shard_dims, f"{name}: non-2D param sharded"
            continue
        if "embed" in name and shape[0] % 2 == 0:
            assert shard_dims == [0], name
        elif "embed" not in name and shape[1] % 2 == 0:
            assert shard_dims == [1], name
        else:
            assert not shard_dims, name
    # vocab 97 is indivisible: THIS config's embedding is the
    # replicated fallback...
    emb = [n for n in plan if "embed" in n]
    assert emb and all(
        not any(p.is_shard() for p in plan[n]) for n in emb)
    # ...and a divisible vocab shards dim 0 (the VocabParallelEmbedding
    # layout; dim 1 would split the tied LM head's contraction)
    paddle.seed(0)
    cfg96 = LlamaConfig(vocab_size=96, hidden_size=16,
                        intermediate_size=32, num_hidden_layers=1,
                        num_attention_heads=2,
                        max_position_embeddings=128,
                        tie_word_embeddings=True)
    plan96 = plan_tp_shardings(LlamaForCausalLM(cfg96), mesh)
    emb96 = [n for n in plan96 if "embed" in n]
    assert emb96 and all(
        [p.get_dim() for p in plan96[n] if p.is_shard()] == [0]
        for n in emb96)
    # an UNTIED lm_head is a Linear(H, V) — (in, out) layout: dim 0 is
    # the hidden CONTRACTION dim, so the plan must shard dim 1 (the
    # vocab OUTPUT dim), never lump it into the vocab-major branch
    paddle.seed(0)
    cfg_untied = LlamaConfig(vocab_size=96, hidden_size=16,
                             intermediate_size=32, num_hidden_layers=1,
                             num_attention_heads=2,
                             max_position_embeddings=128,
                             tie_word_embeddings=False)
    plan_u = plan_tp_shardings(LlamaForCausalLM(cfg_untied), mesh)
    head = [n for n in plan_u if "lm_head" in n]
    assert head and all(
        [p.get_dim() for p in plan_u[n] if p.is_shard()] == [1]
        for n in head)


def test_tp_untied_lm_head_bit_identical():
    """The untied-LM-head config (lm_head weight is (hidden, vocab) —
    the layout whose dim-0 shard would split a contraction): TP tokens
    must still equal single-chip bit-for-bit."""
    cfg = LlamaConfig(vocab_size=96, hidden_size=16, intermediate_size=32,
                      num_hidden_layers=1, num_attention_heads=2,
                      max_position_embeddings=128,
                      tie_word_embeddings=False)

    def build():
        paddle.seed(0)
        return LlamaForCausalLM(cfg)

    prompts = _prompts(3)
    e0 = ContinuousBatchingEngine(build(), **_ENG_KW)
    outs0, _ = e0.run(prompts, max_new_tokens=6, segment=4)
    e1 = TPShardedEngine(build(), mesh=serving_mesh(2), **_ENG_KW)
    outs1, _ = e1.run(prompts, max_new_tokens=6, segment=4)
    for a, b in zip(outs0, outs1):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_bit_identical_to_single_chip():
    """THE interchangeability contract: short prompts, a chunked
    long-context admission, and sampled (not greedy) streams — the TP
    engine's tokens equal the single-chip engine's bit-for-bit."""
    prompts = _prompts(3)
    prompts.append(np.arange(23, dtype=np.int32) % _CFG.vocab_size)
    e0 = ContinuousBatchingEngine(_model(), **_ENG_KW)
    outs0, _ = e0.run(prompts, max_new_tokens=8, segment=4)
    e1 = TPShardedEngine(_model(), mesh=serving_mesh(2), **_ENG_KW)
    outs1, st = e1.run(prompts, max_new_tokens=8, segment=4)
    for a, b in zip(outs0, outs1):
        np.testing.assert_array_equal(a, b)
    assert st["tp"]["degree"] == 2
    assert st["tp"]["kv_sharded"]  # 2 kv heads over 2 shards


def test_tp_engine_chunks_through_the_paged_flash_forward():
    """Chunks of 128 over pages of 128 take ``flash_fwd_paged``; under the
    TP engine that kernel runs inside ``shard_map`` with a kv head a
    shard, and a three-chunk prompt's tokens still equal the single-chip
    engine's."""
    from _jaxpr import pallas_calls

    kw = dict(max_slots=2, max_len=384, page_size=128,
              prompt_buckets=(128,), seed=13)
    prompts = [np.arange(300, dtype=np.int32) * 7 % _CFG.vocab_size,
               np.arange(20, dtype=np.int32) % _CFG.vocab_size]

    def model():
        paddle.seed(0)
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=97, hidden_size=16, intermediate_size=32,
            num_hidden_layers=2, num_attention_heads=2,
            max_position_embeddings=384, tie_word_embeddings=True))

    outs0, _ = ContinuousBatchingEngine(model(), **kw).run(
        prompts, max_new_tokens=6, segment=4)
    e1 = TPShardedEngine(model(), mesh=serving_mesh(2), **kw)
    outs1, st = e1.run(prompts, max_new_tokens=6, segment=4)
    for a, b in zip(outs0, outs1):
        np.testing.assert_array_equal(a, b)
    assert st["tp"]["kv_sharded"]

    i32 = np.int32
    jaxpr = e1._chunk_p.trace(
        e1._params, e1._ks, e1._vs, np.zeros((1, 128), i32),
        e1._tables_np[:1], np.zeros((1,), i32)).jaxpr.jaxpr
    calls = pallas_calls(jaxpr)
    assert [name for name, _ in calls] == ["flash_fwd_paged"] * 2
    assert all("shard_map" in enclosing for _, enclosing in calls)


def test_tp_engine_serial_equals_pipelined():
    """The overlapped scheduler's speculative dispatch must stay
    token-identical on the sharded programs too."""
    prompts = _prompts(4, rng_seed=7)
    mesh = serving_mesh(2)
    e_ser = TPShardedEngine(_model(), mesh=mesh, pipeline=False,
                            **_ENG_KW)
    outs_ser, st_ser = e_ser.run(prompts, max_new_tokens=10, segment=4)
    e_pipe = TPShardedEngine(_model(), mesh=mesh, pipeline=True,
                             **_ENG_KW)
    outs_pipe, st_pipe = e_pipe.run(prompts, max_new_tokens=10, segment=4)
    assert not st_ser["pipelined"] and st_pipe["pipelined"]
    for a, b in zip(outs_ser, outs_pipe):
        np.testing.assert_array_equal(a, b)


def test_tp_warmup_zero_post_warmup_compiles():
    """AOT warmup lowers every (bucket x width) program WITH the mesh
    shardings: a warmed TP engine serves with zero XLA compiles (the
    PR 5 invariant, now per mesh), and a second warmup is fully
    cached."""
    eng = TPShardedEngine(_model(), mesh=serving_mesh(2), **_ENG_KW)
    st = eng.warmup(segment=4)
    assert st["programs"] > 0 and st["cached"] == 0
    prompts = _prompts(3)
    prompts.append(np.arange(23, dtype=np.int32) % _CFG.vocab_size)
    with count_backend_compiles() as compiles:
        outs, _ = eng.run(prompts, max_new_tokens=8, segment=4)
    assert not compiles, (
        f"{len(compiles)} post-warmup compile(s) on a warmed TP engine")
    # the serving-phase watchdog counter stayed clean too
    assert telemetry.counter("xla.compiles_total").value(
        phase="serving") == 0
    st2 = eng.warmup(segment=4)
    assert st2["programs"] == 0 and st2["cached"] > 0
    # and the engine actually produced the reference streams
    ref = ContinuousBatchingEngine(_model(), **_ENG_KW)
    outs0, _ = ref.run(prompts, max_new_tokens=8, segment=4)
    for a, b in zip(outs0, outs):
        np.testing.assert_array_equal(a, b)


def test_tp_engine_leaves_shared_model_unsharded():
    """REGRESSION (bench e8 found it): building a TP engine must NOT
    mutate the shared model's params — a collocated single-chip engine
    over the same model AOT-compiles without shardings, and
    mesh-committed params would make every warmed dispatch raise
    (requests all retire 'failed')."""
    model = _model()
    tp = TPShardedEngine(model, mesh=serving_mesh(2), **_ENG_KW)
    tp.warmup(segment=4)
    from jax.sharding import NamedSharding

    for _, p in model.named_parameters():
        sh = getattr(p._value, "sharding", None)
        assert not isinstance(sh, NamedSharding), \
            "TP engine committed the shared model's params to its mesh"
    sc = ContinuousBatchingEngine(model, **_ENG_KW)
    sc.warmup(segment=4)   # unsharded avals — must match at dispatch
    prompts = _prompts(2)
    with count_backend_compiles() as compiles:
        outs_sc, _ = sc.run(prompts, max_new_tokens=6, segment=4)
    assert not compiles
    outs_tp, _ = tp.run(prompts, max_new_tokens=6, segment=4)
    for a, b in zip(outs_sc, outs_tp):
        np.testing.assert_array_equal(a, b)


def test_tp_degree_one_mesh_still_serves():
    """A degree-1 mesh (single visible device) rides the same code
    path — the degenerate TP group a dev box runs."""
    eng = TPShardedEngine(_model(), mesh=serving_mesh(1), **_ENG_KW)
    outs, st = eng.run(_prompts(2), max_new_tokens=6, segment=4)
    assert st["tp"]["degree"] == 1
    ref = ContinuousBatchingEngine(_model(), **_ENG_KW)
    outs0, _ = ref.run(_prompts(2), max_new_tokens=6, segment=4)
    for a, b in zip(outs0, outs):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ group membership


@pytest.fixture
def gang_store():
    store = TCPStore(is_master=True)
    yield store
    store.close()


def _membership(store, member, tp_degree=2, lease=0.5):
    return TPGroupMembership(store, group_id=0, member_rank=member,
                             tp_degree=tp_degree, lease=lease,
                             interval=0.1, grace=5.0)


def test_member_death_detected_within_lease(gang_store):
    leader = _membership(gang_store, 0).start()
    member = _membership(gang_store, 1).start()
    try:
        assert leader.wait_ready(timeout=10)
        leader.check("pre")  # whole gang: no raise
        member.stop()        # the member process "dies": beats stop
        t0 = time.monotonic()
        deadline = t0 + 10 * leader.lease
        with pytest.raises(PeerFailureError, match="rank 1"):
            while time.monotonic() < deadline:
                leader.check("decode")
                time.sleep(0.05)
            pytest.fail("member death never detected")
        detect_s = time.monotonic() - t0
        # within one lease (+ one poll interval of slack)
        assert detect_s < leader.lease + 3 * leader.interval + 0.5, detect_s
        assert resilience.get_counter("tp.member_dead") >= 1
    finally:
        leader.stop()
        member.stop()


def test_wait_ready_gates_on_the_whole_gang(gang_store):
    leader = _membership(gang_store, 0).start()
    try:
        # the other member never came up: the gate must hold
        assert not leader.wait_ready(timeout=0.5)
        member = _membership(gang_store, 1).start()
        try:
            assert leader.wait_ready(timeout=10)
        finally:
            member.stop()
    finally:
        leader.stop()


def test_member_main_exits_clean_on_announced_shutdown(gang_store):
    leader = _membership(gang_store, 0).start()
    member = _membership(gang_store, 1).start()
    rc_box = {}
    t = threading.Thread(
        target=lambda: rc_box.update(rc=tp_member_main(member, poll=0.05)),
        daemon=True)
    t.start()
    leader.announce_shutdown()  # deliberate release, not a crash
    t.join(10)
    assert not t.is_alive() and rc_box["rc"] == 0
    leader.stop()
    # the announcement must not poison the group id: a RELAUNCHED gang
    # on the same store clears it at start() and can re-form
    leader2 = _membership(gang_store, 0).start()
    assert not leader2.shutdown_announced()
    leader2.stop()


def test_member_main_exits_for_respawn_on_peer_death(gang_store):
    leader = _membership(gang_store, 0).start()
    member = _membership(gang_store, 1).start()
    rc_box = {}
    t = threading.Thread(
        target=lambda: rc_box.update(rc=tp_member_main(member, poll=0.05)),
        daemon=True)
    t.start()
    leader.stop()  # the leader "dies": beats stop, no announcement
    t.join(15)
    assert not t.is_alive() and rc_box["rc"] == 1
    assert resilience.get_counter("tp.group_collapsed") >= 1


def test_member_main_exits_when_gang_store_vanishes(gang_store):
    """ORPHAN GUARD: a member whose gang store died with the supervisor
    has nobody left to respawn its peers or itself — it must exit, not
    watch a vanished gang forever (the leak a real drill surfaced).
    The vanished store is simulated at the probe (closing a live
    native store under in-process clients segfaults the test runner;
    in production the store dies WITH its process)."""
    leader = _membership(gang_store, 0).start()
    member = _membership(gang_store, 1).start()
    rc_box = {}
    t = threading.Thread(
        target=lambda: rc_box.update(rc=tp_member_main(member, poll=0.05)),
        daemon=True)
    t.start()
    time.sleep(0.3)  # let the watch loop arm on the healthy store
    member.shutdown_state = lambda: "unreachable"  # store stops answering
    t.join(60)
    assert not t.is_alive() and rc_box["rc"] == 1
    assert resilience.get_counter("tp.member_store_lost") == 1
    leader.stop()


def test_tp_member_death_fault_site_drill(gang_store):
    """The ``tp.member_death`` registry site: one armed injection makes
    the next membership check read as a gang death — the whole recovery
    path drills without killing a process."""
    leader = _membership(gang_store, 0).start()
    member = _membership(gang_store, 1).start()
    try:
        set_flags({"FLAGS_fault_injection": "tp.member_death:1"})
        with pytest.raises(PeerFailureError, match="injected TP member"):
            leader.check("drill")
        assert resilience.get_counter("tp.member_dead") == 1
        resilience.reset_faults()
        leader.check("after")  # budget consumed: healthy again
    finally:
        leader.stop()
        member.stop()


def test_tp_collective_timeout_fault_site_drill(gang_store):
    """``tp.collective_timeout``: a wedged cross-member collective is
    the same group-fatal verdict as a member death."""
    leader = _membership(gang_store, 0).start()
    member = _membership(gang_store, 1).start()
    try:
        set_flags({"FLAGS_fault_injection": "tp.collective_timeout:1"})
        with pytest.raises(PeerFailureError, match="collective timeout"):
            leader.check("drill")
        assert resilience.get_counter("tp.collective_timeout") == 1
        resilience.reset_faults()
    finally:
        leader.stop()
        member.stop()


# ----------------------------------------- router: one group, one death


def _tp_frontend(**kw):
    eng = TPShardedEngine(_model(), mesh=serving_mesh(2), **_ENG_KW)
    kw.setdefault("max_queue", 32)
    kw.setdefault("segment", 4)
    kw.setdefault("breaker_threshold", 50)
    return ServingFrontend(eng, **kw)


def _sc_frontend(**kw):
    eng = ContinuousBatchingEngine(_model(), **_ENG_KW)
    kw.setdefault("max_queue", 32)
    kw.setdefault("segment", 4)
    kw.setdefault("breaker_threshold", 50)
    return ServingFrontend(eng, **kw)


def test_group_death_is_one_death_not_n(tmp_path):
    """SATELLITE REGRESSION: a TP gang registers as ONE replica, so a
    group collapse must cost exactly one ``fleet.replica_dead``, one
    ``replica_dead`` flight event naming every stranded rid, one
    breaker trip, and ONE failover charge per stranded request — never
    one per member process. Every stranded stream completes on the
    single-chip survivor bit-identical to the uninterrupted run."""
    router = ServingRouter(max_failovers=2)
    tp_id = router.add_replica(_tp_frontend())
    prompts = _prompts(4, rng_seed=11)
    rids = [router.submit(p, max_new_tokens=16) for p in prompts]
    # everything is assigned to the (only) TP replica; let decode start
    for _ in range(2):
        router.step()
    stranded = set(router._replicas[tp_id].assigned)
    assert stranded == set(rids)
    # the survivor joins, then the whole gang dies at once
    router.add_replica(_sc_frontend())
    router.fail_replica(tp_id, "gang member SIGKILLed (drill)")
    res = router.results(wait=True, timeout_s=600)
    assert set(res) >= set(rids)  # zero lost
    want = _single_chip_reference(prompts, rids, 16)
    for rid in rids:
        assert res[rid].status == "ok", res[rid]
        np.testing.assert_array_equal(res[rid].tokens, want[rid])
    # ONE death, however many member processes backed the group
    assert resilience.get_counter("fleet.replica_dead") == 1
    assert resilience.get_counter("fleet.failover") == len(rids)
    assert resilience.get_counter("fleet.failover_budget_exhausted") == 0
    deaths = [e for e in telemetry.flight_recorder().events()
              if e["kind"] == "replica_dead"]
    assert len(deaths) == 1
    assert sorted(deaths[0]["stranded"]) == sorted(rids)
    router.shutdown()


# --------------------------------------------------------- obs fleet CLI


def test_obs_fleet_subcommand_live_and_from_files(capsys, tmp_path):
    """``obs fleet`` renders the roster (state/breaker/assigned) from
    the router-exported gauges, the TP group view from the tp.* series,
    and the death history — live, from a saved snapshot, and from a
    flight dump."""
    from paddle_tpu.tools import obs

    router = ServingRouter(max_failovers=2)
    tp_id = router.add_replica(_tp_frontend())
    router.add_replica(_sc_frontend())
    router.submit(_prompts(1)[0], max_new_tokens=4)
    router.results(wait=True, timeout_s=600)
    router.fail_replica(tp_id, "drill for the event history")
    router.fleet_metrics()  # exports the fleet.replica_* gauges
    assert obs.main(["fleet"]) == 0
    out = capsys.readouterr().out
    assert "replicas (2):" in out
    assert "dead" in out and "open" in out     # the corpse's row
    assert "engine TP degree: 2" in out        # tp.* series present
    assert "replica_dead" in out               # event history
    # from a saved registry snapshot (no live process state needed)
    snap_path = tmp_path / "snap.json"
    snap_path.write_text(json.dumps(telemetry.registry().snapshot()))
    assert obs.main(["fleet", str(snap_path)]) == 0
    out = capsys.readouterr().out
    assert "replicas (2):" in out
    # from a flight dump (the post-mortem artifact)
    dump_path = telemetry.flight_recorder().dump("fleet_test", force=True)
    assert dump_path
    assert obs.main(["fleet", dump_path]) == 0
    out = capsys.readouterr().out
    assert "replicas (2):" in out and "replica_dead" in out
    # garbage path is a usage error, not a crash
    assert obs.main(["fleet", str(tmp_path / "nope.json")]) == 2
    router.shutdown()


# ------------------------------------- flagship: multi-process TP drill


_TP_FLEET_SCRIPT = """
import os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.frontend import ServingFrontend
from paddle_tpu.models.remote import RPC_MASTER_ENV, replica_main
from paddle_tpu.models.serving import ContinuousBatchingEngine
from paddle_tpu.models.tp_serving import (
    TPShardedEngine, serving_mesh, tp_replica_main)
from paddle_tpu.distributed.store import TCPStore

CFG = LlamaConfig(vocab_size=97, hidden_size=16, intermediate_size=32,
                  num_hidden_layers=2, num_attention_heads=2,
                  max_position_embeddings=128, tie_word_embeddings=True)
TP_DEGREE = 2
ENG_KW = dict(max_slots=2, max_len=64, prompt_buckets=(8, 16),
              do_sample=True, temperature=0.9, seed=13)


def build_tp():
    paddle.seed(0)
    model = LlamaForCausalLM(CFG)
    eng = TPShardedEngine(model, mesh=serving_mesh(TP_DEGREE), **ENG_KW)
    return ServingFrontend(eng, max_queue=32, segment=4,
                           breaker_threshold=50)


def build_single():
    paddle.seed(0)
    model = LlamaForCausalLM(CFG)
    eng = ContinuousBatchingEngine(model, **ENG_KW)
    return ServingFrontend(eng, max_queue=32, segment=4,
                           breaker_threshold=50)


if __name__ == "__main__":
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    # publish every launch rank's pid on the FLEET store so the drill
    # can SIGKILL a gang MEMBER (the supervisor's gang store is private)
    endpoint = os.environ[RPC_MASTER_ENV]
    host, _, port = endpoint.rpartition(":")
    st = TCPStore(host or "127.0.0.1", int(port))
    st.set(f"tp/pid/{rank}", str(os.getpid()))
    if rank < TP_DEGREE:
        # ranks 0..TP_DEGREE-1 form TP group 0; member 0 leads and is
        # addressable as worker "replica0" (fleet replica id 0)
        raise SystemExit(tp_replica_main(build_tp, TP_DEGREE, rank=rank,
                                         member_lease=0.75))
    # rank TP_DEGREE is the single-chip replica, fleet replica id 1
    raise SystemExit(replica_main(build_single, rank=1))
"""


def _stub(rank):
    from paddle_tpu.models.remote import RemoteFrontend

    return RemoteFrontend(f"replica{rank}", timeout=60.0,
                          health_timeout=10.0, retry_attempts=2,
                          resend_after=30.0, results_wait=0.1)


@pytest.mark.slow
def test_tp_gang_fleet_member_death_failover_and_rejoin(tmp_path):
    """THE acceptance drill: launch_fleet with one TP-gang replica (2
    member processes) + one single-chip replica under trickled traffic;
    SIGKILL the non-leader gang MEMBER mid-decode → the leader detects
    the broken gang within one membership lease and dies with it, the
    router marks the GROUP dead (one breaker trip, ONE replica death),
    zero requests are lost and every failover stream is bit-identical
    to the uninterrupted run; the supervisor respawns the dead ranks,
    the gang re-forms (warm-before-admit) and serves again."""
    import os
    import signal

    from paddle_tpu.distributed import rpc
    from paddle_tpu.models.remote import RPC_MASTER_ENV
    from paddle_tpu.models.router import launch_fleet

    script = tmp_path / "tp_replica.py"
    script.write_text(textwrap.dedent(_TP_FLEET_SCRIPT))
    store = rpc.init_rpc("router", rank=0, world_size=3)
    endpoint = f"127.0.0.1:{store.port}"
    fleet_store = TCPStore(port=store.port)
    router = ServingRouter(store=fleet_store, lease=1.5,
                           heartbeat_interval=0.1, max_failovers=3)
    rc_box = {}
    supervisor = threading.Thread(
        target=lambda: rc_box.update(rc=launch_fleet(
            str(script), n_replicas=3, max_restarts=4,
            env={RPC_MASTER_ENV: endpoint},
            backoff_base=0.01, poll_interval=0.05)),
        daemon=True)
    supervisor.start()
    try:
        # group leader = worker "replica0" (fleet id 0); single-chip =
        # "replica1" (fleet id 1)
        for rep in (0, 1):
            rpc.get_worker_info(f"replica{rep}", timeout=300)
            router.add_replica(_stub(rep), replica_id=rep)
        pids = {r: int(fleet_store.get(f"tp/pid/{r}").decode())
                for r in (0, 1, 2)}

        # warm pass: first-traffic compiles land here
        warm = [router.submit(p, max_new_tokens=2)
                for p in _prompts(2, rng_seed=7)]
        wres = router.results(wait=True, timeout_s=600)
        assert all(wres[r].status == "ok" for r in warm)

        # ---- the kill: SIGKILL the NON-LEADER gang member while the
        # group decodes, then keep TRICKLING traffic through the death
        # window — the tiny model drains a fixed batch faster than the
        # lease can convict, and the acceptance drill is "under
        # trickled traffic" precisely so work is in flight whenever the
        # death lands
        book = {}   # rid -> (prompt, max_new)
        for p in _prompts(10, rng_seed=11):
            book[router.submit(p, max_new_tokens=48)] = (p, 48)
        deadline = time.monotonic() + 120
        while (not router._replicas[0].assigned
               and time.monotonic() < deadline):
            router.step()
            time.sleep(0.02)
        assert router._replicas[0].assigned, \
            "drill needs in-flight work on the TP group"
        t_kill = time.monotonic()
        os.kill(pids[1], signal.SIGKILL)   # launch rank 1 = gang member
        trickle = iter(_prompts(600, rng_seed=17))
        deadline = time.monotonic() + 120
        while (router._replicas[0].state != "dead"
               and time.monotonic() < deadline):
            p = next(trickle, None)
            if p is not None:
                book[router.submit(p, max_new_tokens=8)] = (p, 8)
            router.step()
            time.sleep(0.05)
        # the whole gang read as ONE dead replica within the leases
        # (member lease 0.75s -> leader exits; router lease 1.5s)
        assert router._replicas[0].state == "dead"
        detect_s = time.monotonic() - t_kill
        assert detect_s < 60, detect_s
        assert resilience.get_counter("fleet.replica_dead") == 1
        res_b = router.results(wait=True, timeout_s=600)
        rids_b = list(book)
        assert set(res_b) >= set(rids_b)   # zero requests lost
        want_b = _single_chip_reference([book[r][0] for r in rids_b],
                                        rids_b,
                                        [book[r][1] for r in rids_b])
        for rid in rids_b:
            assert res_b[rid].status == "ok", (rid, res_b[rid])
            np.testing.assert_array_equal(res_b[rid].tokens, want_b[rid])

        # ---- respawn: both gang ranks come back, the gang re-forms
        # (leader waits for the member: warm-before-admit), and the
        # group returns to rotation
        deadline = time.monotonic() + 300
        new_leader_pid = None
        while time.monotonic() < deadline:
            try:
                p = int(fleet_store.get("tp/pid/0").decode())
            except Exception:
                p = pids[0]
            if p != pids[0]:
                new_leader_pid = p
                break
            time.sleep(0.2)
        assert new_leader_pid is not None, "gang leader never respawned"
        rpc.get_worker_info("replica0", timeout=300)
        router.add_replica(_stub(0), replica_id=0, warmup=True)
        rejoin = [router.submit(p, max_new_tokens=4)
                  for p in _prompts(4, rng_seed=13)]
        res_c = router.results(wait=True, timeout_s=600)
        assert all(res_c[r].status == "ok" for r in rejoin)
        assert router._replicas[0].served > 0  # the rejoined gang served
    finally:
        router.shutdown()
        supervisor.join(120)
        rpc.shutdown()
        fleet_store.close()
    assert rc_box.get("rc") == 0  # every worker exited clean
