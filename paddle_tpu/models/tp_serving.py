"""Tensor-parallel serving replicas: one "replica" spans a TP gang of
chips behind a ProcessMesh, with single-chip failure semantics preserved.

The serving fleet (router → frontend → engine) saturates at one chip per
replica, so it cannot serve models that don't fit a single device — the
production default. This module makes one replica a **TP group**:

* :class:`TPShardedEngine` — a ``ContinuousBatchingEngine`` whose
  parameters and paged KV pools are laid out over a ``ProcessMesh``
  carrying a tensor-parallel axis (default ``"mp"``, the training
  stack's axis name). The sharding plan reuses the training TP
  placements (``Shard``/``Replicate`` resolved through
  ``distributed.api.to_named_sharding``, applied at engine snapshot
  time — the model object itself is never mutated, so a collocated
  single-chip engine can share it): embeddings and
  the LM head shard the vocab dim, projection weights shard the OUTPUT
  feature dim, and the KV pools shard the kv-head dim. GSPMD derives the
  collectives at compile time; the plan deliberately shards only output/
  gather dims — never a contraction — so the partitioned programs emit
  **bit-identical token streams** to the single-chip engine (asserted in
  tests/test_tp_serving.py: a TP group and a single-chip replica are
  interchangeable behind the router, and failover across them stays
  bit-exact). AOT ``warmup()`` lowers every (bucket × width) program
  with the committed shardings in the avals, so a warmed TP engine still
  records ZERO post-warmup compiles — now per mesh.
* :class:`TPGroupMembership` — gang membership for the group's member
  PROCESSES, riding the ``distributed/gang.py`` machinery
  (``PeerFailureDetector`` over a group-scoped heartbeat prefix): every
  member beats ``tp/{group}/hb/{member}``; ``check()`` raises
  ``PeerFailureError`` within one ``FLAGS_heartbeat_ttl`` lease of any
  member dying. The group fails as ONE unit: the leader stops serving
  (its fleet heartbeat lapses → the router trips the GROUP's breaker and
  fails over via ``token_base`` resubmission, exactly like a single-chip
  replica death), and surviving members exit so the supervisor
  (``launch(restart_policy="worker")``) respawns the gang; the re-formed
  group waits ``wait_ready()`` (every member fresh) and re-enters
  rotation warm-before-admit.
* :func:`tp_replica_main` / :func:`tp_member_main` — worker-process
  entries under ``launch_fleet``: member 0 (the leader) hosts the
  group's ``ReplicaServer`` (``models/remote.py``) and is the one
  addressable frontend the router sees for the whole gang; members > 0
  run the membership watch loop only.

Deterministic fault sites: ``tp.member_death`` (the membership check
behaves as if a gang member died) and ``tp.collective_timeout`` (a
cross-member collective wedged past its budget — the same group-fatal
verdict). Counters land under ``tp.*`` in the resilience ledger.
"""
from __future__ import annotations

import contextlib
import os
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..core import telemetry
from ..core.resilience import (
    Deadline,
    InjectedFault,
    PeerFailureError,
    bump_counter,
    inject,
    logger,
)
from ..distributed.api import to_named_sharding
from ..distributed.placement import Replicate, Shard
from ..distributed.process_mesh import ProcessMesh
from .generation import sequence_keeps
from .serving import ContinuousBatchingEngine

__all__ = ["TPShardedEngine", "TPGroupMembership", "plan_tp_shardings",
           "tp_replica_main", "tp_member_main", "serving_mesh"]

# tp.* metrics (module-level handles — see serving.py note). Documented
# in README "Observability"; CI-gated against orphaning.
_M_TP_MEMBERS = telemetry.gauge(
    "tp.group_members", "declared member count of this process's TP "
    "serving group")
_M_TP_DEGREE = telemetry.gauge(
    "tp.engine_degree", "tensor-parallel degree of this process's "
    "serving engine (mesh size along the TP axis)")


def serving_mesh(tp_degree, tp_axis="mp", devices=None) -> ProcessMesh:
    """A 1-D ``ProcessMesh`` over the first ``tp_degree`` visible devices
    — the serving-side convenience for building a TP engine's mesh (the
    training stack builds richer meshes via ``dist.init_mesh``).
    ``devices`` selects an explicit device subset instead (e.g. a second
    TP group beside an existing one on chips 4..7); the mesh is built
    over THOSE devices' ids, not 0..tp_degree-1."""
    if devices is None:
        n = len(jax.devices())
        ids = np.arange(tp_degree)
    else:
        n = len(devices)
        ids = np.asarray([getattr(d, "id", d) for d in devices]
                         [:tp_degree])
    if tp_degree > n:
        raise ValueError(
            f"tp_degree {tp_degree} exceeds the {n} visible devices; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N for "
            "virtual CPU meshes")
    return ProcessMesh(ids, [tp_axis])


def plan_tp_shardings(model, mesh: ProcessMesh, tp_axis="mp") -> dict:
    """Megatron-style sharding plan for a causal-LM's parameters as
    ``{param name: placements list}`` — the assignment
    ``fleet.mp_layers`` declares, restricted to the **output-stationary**
    subset that keeps serving bit-exact:

    * embedding tables (vocab-major ``(V, H)``): ``Shard(0)`` over the
      vocab dim — a partitioned gather (and, tied, a ``transpose_y``
      output-dim matmul for the LM head): no contraction is split, the
      ``VocabParallelEmbedding`` layout;
    * every other 2-D weight — projections AND an untied LM head
      (paddle ``Linear(H, V)`` weights are ``(in, out)``): ``Shard(1)``
      over the OUTPUT features (``ColumnParallelLinear``'s layout; for
      the LM head that IS the vocab dim). The Megatron row-parallel
      half (``Shard(0)`` on o_proj/down_proj inputs) is deliberately
      NOT used: splitting a contraction dim changes the reduction
      order, and the fleet failover contract needs TP-group and
      single-chip token streams bit-identical;
    * anything indivisible (or 1-D): ``Replicate``.
    """
    degree = mesh.get_dim_size(tp_axis)
    axis = mesh.dim_names.index(tp_axis)
    plan = {}
    for name, p in model.named_parameters():
        shape = tuple(p.shape)
        pl = [Replicate()] * mesh.ndim
        if len(shape) == 2:
            # ONLY embedding tables are vocab-major; an untied lm_head
            # is a Linear whose dim 0 is the HIDDEN (contraction) dim —
            # lumping it in here would shard a contraction and break
            # bit-exactness on a real mesh
            if "embed" in name and shape[0] % degree == 0:
                pl[axis] = Shard(0)
            elif "embed" not in name and shape[1] % degree == 0:
                pl[axis] = Shard(1)
        plan[name] = pl
    return plan


class TPShardedEngine(ContinuousBatchingEngine):
    """``ContinuousBatchingEngine`` sharded tensor-parallel over a
    ``ProcessMesh``.

    Usage::

        mesh = serving_mesh(tp_degree=4)          # or dist.init_mesh
        eng = TPShardedEngine(model, max_slots=8, max_len=512, mesh=mesh)
        eng.warmup(segment=16)   # AOT per (bucket x width) — per MESH
        # ... identical surface (and identical token streams) from here

    The engine's scheduler, bisection, pipelining, deadlines, and
    sampling are untouched — only the array layout changes: parameters
    follow :func:`plan_tp_shardings` (overridable via ``plan=``), the
    paged KV pools shard the kv-head dim when the TP degree divides it,
    and every host-fabricated operand is committed replicated before a
    dispatch (an AOT executable compiled for the mesh refuses
    uncommitted single-device operands). ``stats()['tp']`` reports the
    degree and the cumulative host cost of those placements
    (``put_s``) — bench e8 gates it as ``tp_dispatch_overhead_pct``.
    """

    def __init__(self, model, max_slots, max_len, mesh=None, tp_axis="mp",
                 plan=None, **kwargs):
        if any(k is not None and k[0] == "state"
               for k in sequence_keeps(model)):
            # the state's kv heads would shard cleanly, but nothing here
            # places a state or partitions its kernels yet
            raise NotImplementedError(
                f"TPShardedEngine cannot serve {type(model).__name__}: it "
                "keeps a recurrent state a slot, and this engine shards "
                "page pools; serve it with ContinuousBatchingEngine on one "
                "chip (ROADMAP M4)")
        if hasattr(model, "kv_page_shapes"):
            # this engine shards the page pools over kv heads and plans
            # placements for a dense block's projections; a latent cache
            # has no kv heads and sparse experts no plan (ROADMAP M3)
            raise NotImplementedError(
                f"TPShardedEngine cannot serve {type(model).__name__}: its "
                "latent attention cache has no kv-head axis to shard and "
                "its sparse experts have no tensor-parallel plan; serve it "
                "with ContinuousBatchingEngine on one chip")
        if mesh is None:
            from ..distributed.process_mesh import get_mesh

            mesh = get_mesh()
        if mesh is None:
            raise ValueError("TPShardedEngine needs a mesh= (ProcessMesh "
                             "with the TP axis) or a global mesh "
                             "(dist.init_mesh)")
        if tp_axis not in mesh.dim_names:
            raise ValueError(
                f"mesh {mesh!r} has no {tp_axis!r} axis; serving TP "
                f"shards over it (dims: {mesh.dim_names})")
        self._mesh = mesh
        self._tp_axis = tp_axis
        self._tp_degree = int(mesh.get_dim_size(tp_axis))
        jmesh = mesh.jax_mesh()
        self._jmesh = jmesh
        self._repl = NamedSharding(jmesh, PartitionSpec())
        self._tp_put_s = 0.0
        super().__init__(model, max_slots, max_len, **kwargs)
        # resolve the plan's placements into concrete shardings ONCE.
        # Crucially the MODEL is never mutated: params are laid onto the
        # mesh at snapshot time (_param_snapshot, cached per source
        # array), so a collocated single-chip engine sharing the same
        # model keeps seeing unsharded params — its AOT executables
        # (compiled without shardings) would reject mesh-committed
        # inputs otherwise.
        plan = plan if plan is not None else plan_tp_shardings(
            model, mesh, tp_axis=tp_axis)
        self._plan_shardings = {
            name: to_named_sharding(mesh, pl)
            for name, pl in plan.items()}
        self._shard_cache: dict = {}   # name -> (source array, sharded)
        with self._swap_lock:
            # the buffer dict is CLOSED OVER by the compiled-program
            # bodies (_build_programs): update it in place with
            # replicated copies, leaving the model's own buffers alone
            for name in list(self._buffers):
                self._buffers[name] = jax.device_put(
                    self._buffers[name], self._repl)
        # KV pools shard the kv-head dim (the memory the TP group exists
        # to split); an indivisible head count stays replicated
        kv_heads = int(self._ks[0].shape[2])
        if kv_heads % self._tp_degree == 0:
            kv_pl = [Replicate()] * mesh.ndim
            kv_pl[mesh.dim_names.index(tp_axis)] = Shard(2)
            kv_sh = to_named_sharding(mesh, kv_pl)
        else:
            kv_sh = self._repl
        self._kv_sharding = kv_sh
        self._ks = [jax.device_put(k, kv_sh) for k in self._ks]
        self._vs = [jax.device_put(v, kv_sh) for v in self._vs]
        # the dynamic page table is re-uploaded on every grant
        # (_tables_device below commits it replicated); drop any copy
        # the base constructor may have cached un-meshed
        self._tables_active = None
        if telemetry.enabled():
            _M_TP_DEGREE.set(self._tp_degree)

    def _param_snapshot(self):
        """Mesh-sharded param snapshot, cached per SOURCE array: a
        repeated ``start()``/``warmup()`` over unchanged weights reuses
        the committed shards (no re-transfer); a swapped weight (new
        source array) is re-laid out."""
        out = {}
        for name, v in super()._param_snapshot().items():
            hit = self._shard_cache.get(name)
            if hit is not None and hit[0] is v:
                out[name] = hit[1]
                continue
            sv = jax.device_put(
                v, self._plan_shardings.get(name, self._repl))
            self._shard_cache[name] = (v, sv)
            out[name] = sv
        return out

    def _kernel_scope(self):
        # heads (and kv heads) split over the TP axis, as the plan's
        # column-parallel projections and the kv-head-sharded pools do
        from ..ops.pallas import kernel_mesh

        return kernel_mesh(self._jmesh, head_axis=self._tp_axis)

    # ---------------------------------------------------- aval overrides

    def _sds(self, x):
        # the committed sharding must ride the AOT lowering: an
        # executable compiled without it refuses the sharded params/pools
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                    sharding=getattr(x, "sharding", None))

    def _op_aval(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._repl)

    # ------------------------------------------------- operand placement

    def _commit(self, a):
        """One host operand committed replicated on the mesh (the AOT
        executables were lowered with replicated operand avals). A jax
        array reshards device-side — forcing it through np.asarray
        would be a blocking D2H + re-upload per operand per dispatch,
        inflating exactly the tp_put_s the e8 gate bounds."""
        if isinstance(a, jax.Array):
            sh = a.sharding
            if isinstance(sh, NamedSharding) and sh.mesh == self._jmesh:
                return a
            return jax.device_put(a, self._repl)
        return jax.device_put(np.asarray(a), self._repl)

    def _call(self, key, fallback, params, ks, vs, *rest):
        t0 = time.monotonic()
        rest = tuple(self._commit(a) for a in rest)
        self._tp_put_s += time.monotonic() - t0
        return super()._call(key, fallback, params, ks, vs, *rest)

    def _key_zeros(self, shape):
        # commit the cached greedy zero-keys once instead of re-placing
        # them on every dispatch through _commit
        arr = self._zeros_cache.get(shape)
        if arr is None:
            arr = jax.device_put(
                np.zeros(shape, np.uint32).astype(self._zero_key.dtype),
                self._repl)
            self._zeros_cache[shape] = arr
        return arr

    def _limits_device(self):
        if self._limits_dev is None:
            self._limits_dev = jax.device_put(self._limits, self._repl)
        return self._limits_dev

    def _tables_device(self):
        # page GRANTS invalidate the device table like admissions
        # invalidate the limits: re-upload the numpy rows committed
        # replicated on the mesh (contents change, shape never does)
        if self._tables_active is None:
            self._tables_active = jax.device_put(
                self._tables_np[:self.max_slots], self._repl)
        return self._tables_active

    def tp_stats(self) -> dict:
        """TP accounting: the degree, axis, and cumulative host seconds
        spent committing dispatch operands onto the mesh (``put_s`` —
        the TP-specific dispatch overhead bench e8 gates)."""
        return {"degree": self._tp_degree, "axis": self._tp_axis,
                "put_s": self._tp_put_s,
                "kv_sharded": self._kv_sharding is not self._repl}

    def stats(self):
        out = super().stats()
        out["tp"] = self.tp_stats()
        return out


# ------------------------------------------------------ group membership

class TPGroupMembership:
    """Gang membership for one TP serving group's member processes.

    Reuses the gang-recovery machinery (``distributed/gang.py``): every
    member heartbeats ``{prefix}/{group}/hb/{member}`` on the shared
    store, and :meth:`check` raises :class:`PeerFailureError` naming the
    dead member within one lease — the group-fatal verdict. The GROUP
    fails as one unit (the leader stops serving; members exit for the
    supervisor to respawn), so the router sees exactly one replica
    death: one breaker trip, one failover charge per stranded request.

    ``wait_ready()`` is the warm-before-admit gate on (re)formation: the
    leader must not host (or re-register) the group's frontend until
    every member's beat is fresh — a half-formed gang serving traffic
    would die again immediately on the first membership check.
    """

    def __init__(self, store, group_id, member_rank, tp_degree,
                 lease=None, interval=None, grace=None, prefix="tp"):
        from ..distributed.gang import GangContext, PeerFailureDetector

        self.store = store
        self.group_id = int(group_id)
        self.member_rank = int(member_rank)
        self.tp_degree = int(tp_degree)
        self.prefix = f"{prefix}/{self.group_id}/hb"
        self._shutdown_key = f"{prefix}/{self.group_id}/shutdown"
        self._ctx = GangContext(store, rank=self.member_rank,
                                world_size=self.tp_degree)
        self.detector = PeerFailureDetector(
            self._ctx, lease=lease, interval=interval, grace=grace,
            prefix=self.prefix)
        self.lease = self.detector.lease
        self.interval = self.detector.interval

    def start(self):
        """Arm the detector and begin beating for this member. A STALE
        shutdown announcement from the group's previous life on this
        store is cleared first — one clean shutdown must not poison the
        group id forever (a relaunched gang's members would read it and
        exit 0 before the gang could ever re-form)."""
        with contextlib.suppress(ConnectionError, TimeoutError,
                                 RuntimeError):
            if self.store.check(self._shutdown_key):
                self.store.delete_key(self._shutdown_key)
        self.detector.start(beat=True)
        if telemetry.enabled():
            _M_TP_MEMBERS.set(self.tp_degree, group=str(self.group_id))
        return self

    def stop(self):
        self.detector.stop()

    def wait_ready(self, timeout=None) -> bool:
        """Block until every OTHER member's beat is fresh (within one
        lease). The leader calls this before hosting the frontend —
        re-entering rotation with a partial gang would trip again on
        the first check."""
        deadline = Deadline(timeout)
        need = set(range(self.tp_degree)) - {self.member_rank}
        while True:
            now = time.time()  # wall-clock: x-process store beats
            fresh = set()
            with contextlib.suppress(ConnectionError, TimeoutError,
                                     RuntimeError):
                for r in need:
                    t = self.store.last_heartbeat(r, prefix=self.prefix)
                    if t is not None and now - t <= self.lease:
                        fresh.add(r)
            if fresh >= need:
                return True
            if deadline.expired():
                return False
            time.sleep(min(self.interval, 0.05))

    def check(self, phase="tp-serving"):
        """Raise :class:`PeerFailureError` when any gang member died
        (lease-expired beat), the ``tp.member_death`` drill site fires,
        or the ``tp.collective_timeout`` site fires (a wedged
        cross-member collective is the same group-fatal verdict: the
        gang's compiled program cannot make progress without every
        member)."""
        try:
            inject("tp.member_death")
        except InjectedFault as e:
            bump_counter("tp.member_dead")
            raise PeerFailureError(
                f"injected TP member death in group {self.group_id}",
                rank=None, phase=phase) from e
        try:
            inject("tp.collective_timeout")
        except InjectedFault as e:
            bump_counter("tp.collective_timeout")
            raise PeerFailureError(
                f"injected TP collective timeout in group "
                f"{self.group_id}", rank=None, phase=phase) from e
        try:
            self.detector.check(phase)
        except PeerFailureError:
            bump_counter("tp.member_dead")
            raise

    # -------------------------------------------------- clean shutdown

    def announce_shutdown(self):
        """Leader marks the group's exit DELIBERATE so members exit 0
        (a member must distinguish 'leader released us' from 'leader
        died' — only the latter is a crash the supervisor respawns)."""
        with contextlib.suppress(Exception):
            self.store.set(self._shutdown_key, b"1")

    def shutdown_state(self) -> str:
        """ONE store round-trip answering both member-loop questions:
        ``"announced"`` (deliberate group shutdown — exit 0),
        ``"clear"`` (keep watching), or ``"unreachable"`` (the gang
        store is gone; the detector deliberately reads a partitioned
        store as 'no evidence', so a member needs THIS verdict to
        notice its control plane died for good and exit instead of
        watching a vanished gang forever)."""
        try:
            return ("announced" if self.store.check(self._shutdown_key)
                    else "clear")
        except (ConnectionError, TimeoutError, RuntimeError):
            return "unreachable"

    def shutdown_announced(self) -> bool:
        return self.shutdown_state() == "announced"


# ------------------------------------------------ worker-process entries

def tp_member_main(membership: TPGroupMembership, poll=0.1) -> int:
    """Serve loop for a NON-leader gang member: beat, watch the peers,
    exit 0 on an announced (deliberate) group shutdown, exit 1 when a
    peer dies — the supervisor respawns this rank, the re-formed gang
    passes the leader's ``wait_ready`` gate, and the group returns to
    rotation."""
    # formation gate: a respawned member must WAIT for the rest of the
    # gang to beat fresh instead of reading a dead peer's stale beat as
    # an instant verdict — without this, members respawned ahead of the
    # leader thrash exit-1/respawn cycles through the restart budget
    if not membership.wait_ready(timeout=max(membership.detector.grace,
                                             30.0)):
        bump_counter("tp.group_form_timeout")
        logger.error(
            "tp group %d member %d: gang never re-formed; exiting",
            membership.group_id, membership.member_rank)
        membership.stop()
        return 1
    misses = 0
    while True:
        st = membership.shutdown_state()
        if st == "unreachable":
            # the gang store died with the supervisor: nobody is left to
            # respawn peers OR this process — an orphaned member looping
            # on a vanished store would leak forever
            misses += 1
            if misses >= 5:
                bump_counter("tp.member_store_lost")
                logger.error(
                    "tp group %d member %d lost the gang store; exiting",
                    membership.group_id, membership.member_rank)
                membership.stop()
                return 1
            time.sleep(poll)
            continue
        misses = 0
        if st == "announced":
            membership.stop()
            return 0
        try:
            membership.check("member-watch")
        except PeerFailureError as e:
            if membership.shutdown_announced():
                membership.stop()
                return 0
            bump_counter("tp.group_collapsed")
            logger.warning(
                "tp group %d member %d: %s; exiting for respawn",
                membership.group_id, membership.member_rank, e)
            membership.stop()
            return 1
        time.sleep(poll)


def tp_replica_main(build_frontend, tp_degree, rank=None, group_id=None,
                    member_rank=None, fleet_prefix="fleet",
                    group_store=None, member_lease=None,
                    member_grace=None, **replica_kwargs) -> int:
    """Entry point for one TP-group member process under
    ``launch_fleet``. ``rank`` (default ``$PADDLE_TRAINER_ID``) maps to
    ``(group_id, member_rank) = divmod(rank, tp_degree)`` unless given
    explicitly — mixed fleets (TP groups beside single-chip replicas)
    pass them per rank.

    Member 0 is the GROUP LEADER: it waits for the whole gang
    (``wait_ready``, warm-before-admit), then hosts ``build_frontend()``
    behind a ``ReplicaServer`` addressed as ``replica{group_id}`` and
    heartbeats the FLEET prefix under the group id — to the router the
    gang is one replica. Members > 0 run :func:`tp_member_main`. Any
    member death collapses the group: the leader's serve loop checks
    membership each turn and exits 1 (``models/remote.py replica_main``
    ``group=`` hook), its fleet heartbeat lapses within one lease, the
    router trips the group breaker and fails over — then the supervisor
    respawns the dead ranks and the re-formed gang rejoins.

    The membership store defaults to the supervisor's gang store
    (``$PADDLE_GANG_STORE``)."""
    from ..distributed.gang import GANG_STORE_ENV, GENERATION_ENV
    from ..distributed.store import TCPStore

    if rank is None:
        rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if group_id is None or member_rank is None:
        group_id, member_rank = divmod(int(rank), int(tp_degree))
    if group_store is None:
        endpoint = os.environ[GANG_STORE_ENV]
        host, _, port = endpoint.rpartition(":")
        group_store = TCPStore(host or "127.0.0.1", int(port))
    membership = TPGroupMembership(
        group_store, group_id, member_rank, tp_degree,
        lease=member_lease, grace=member_grace).start()
    if int(os.environ.get(GENERATION_ENV, "0") or 0) > 0:
        # a respawned rank re-forming its gang after a member death
        bump_counter("tp.member_rejoined")
    if member_rank != 0:
        return tp_member_main(membership)
    # leader: the gang must be whole BEFORE the group becomes
    # addressable (warm-before-admit — a partial gang would collapse on
    # its first membership check, flapping the router's breaker)
    if not membership.wait_ready(timeout=max(membership.detector.grace,
                                             30.0)):
        bump_counter("tp.group_form_timeout")
        logger.error("tp group %d never formed (%d members expected); "
                     "exiting for respawn", group_id, tp_degree)
        membership.stop()
        return 1
    from .remote import replica_main

    return replica_main(build_frontend, rank=group_id,
                        worker_name=f"replica{group_id}",
                        fleet_prefix=fleet_prefix, group=membership,
                        **replica_kwargs)
