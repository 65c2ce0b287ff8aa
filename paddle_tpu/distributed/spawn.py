"""paddle.distributed.spawn — programmatic multi-process launch.

Analog of /root/reference/python/paddle/distributed/spawn.py:463 (spawn →
_spawn: multiprocessing with per-rank env preparation + _func_wrapper that
bootstraps the parallel env before calling the user function). The
notebook/script-friendly twin of the ``launch`` CLI: same TCPStore
rendezvous + PADDLE_* env contract (launch/__init__.py Pod), but the
worker is a picklable Python FUNCTION instead of an entry script, run via
``multiprocessing``'s spawn context (fresh interpreters — each process is
its own jax controller, exactly the multi-host TPU pod shape).

Each worker gets PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM/PADDLE_MASTER set
BEFORE the user function runs and the parallel env initialized
(dist.init_parallel_env → jax.distributed.initialize), so the function
body starts with the global mesh view — reference _func_wrapper semantics.
"""
from __future__ import annotations

import multiprocessing
import os
import time
import traceback

__all__ = ["spawn", "MultiprocessContext"]


def _worker(func, args, rank, nprocs, master, extra_env, init_env,
            err_queue):
    # env BEFORE any backend touch: jax is imported (module level) but its
    # XLA client is lazy until first device use — init_parallel_env relies
    # on exactly this window (collective.py init_parallel_env NOTE)
    os.environ.update(extra_env or {})
    os.environ.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "PADDLE_RANK_IN_NODE": str(rank),
        "PADDLE_LOCAL_SIZE": str(nprocs),
    })
    try:
        if init_env:
            from . import init_parallel_env

            init_parallel_env()
        func(*args)
    except BaseException:
        err_queue.put((rank, traceback.format_exc()))
        raise


class MultiprocessContext:
    """Returned by spawn(join=False) (reference MultiprocessContext):
    ``join()`` waits and re-raises the first worker failure."""

    def __init__(self, processes, err_queue):
        self.processes = processes
        self._err_queue = err_queue
        self._tracebacks: dict[int, str] = {}

    def _drain(self):
        # queue must be drained WHILE joining: a failing worker's feeder
        # thread blocks on a full pipe at exit if nobody reads (the
        # documented multiprocessing join/queue deadlock)
        import queue as _q

        while True:
            try:
                rank, tb = self._err_queue.get_nowait()
            except (_q.Empty, OSError, ValueError):
                return
            self._tracebacks[rank] = tb

    def join(self, timeout=None):
        # MONOTONIC deadline: an NTP step during a long join must not
        # expire (or extend) the caller's wall-clock budget
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            self._drain()
            alive = [p for p in self.processes if p.exitcode is None]
            if not alive:
                break
            if deadline is not None and time.monotonic() >= deadline:
                break
            alive[0].join(0.1)
        self._drain()
        still_alive = [i for i, p in enumerate(self.processes)
                       if p.exitcode is None]
        if still_alive:
            # a timed-out join is a reportable outcome, not a silent one:
            # the caller sees False AND the ledger/log name the stragglers
            from ..core.resilience import bump_counter, logger

            bump_counter("spawn.join_timeout")
            logger.warning(
                "spawn join timed out after %ss; workers still alive: "
                "ranks %s", timeout, still_alive)
        failed = [(p, i) for i, p in enumerate(self.processes)
                  if p.exitcode not in (0, None)]
        if failed:
            p, rank = failed[0]
            tb = self._tracebacks.get(rank)
            raise RuntimeError(
                f"spawned worker {rank} failed (exitcode {p.exitcode})"
                + (f":\n{tb}" if tb else "")
                + (f"\n({len(failed)} workers failed: "
                   f"{[r for _, r in failed]})" if len(failed) > 1 else ""))
        return all(p.exitcode == 0 for p in self.processes)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Run ``func(*args)`` in ``nprocs`` ranked processes.

    Reference surface (spawn.py:463): ``nprocs=-1`` means one worker per
    visible device group — here one per host process is the TPU-native
    unit, so -1 resolves to ``PADDLE_TRAINERS_NUM`` or 1. ``options``:
    ``master`` ("host:port" of an existing TCPStore; one is created when
    absent), ``env`` (extra per-worker environment), ``init_env=False`` to
    skip the automatic init_parallel_env. With ``join=True`` (default)
    blocks until every worker exits, re-raising the first failure;
    ``join=False`` returns a :class:`MultiprocessContext`.
    """
    unknown = set(options) - {"master", "env", "init_env"}
    if unknown:
        raise ValueError(f"spawn: unsupported options {sorted(unknown)}; "
                         "supported: master, env, init_env")
    if nprocs == -1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if nprocs < 1:
        raise ValueError(f"nprocs must be >= 1, got {nprocs}")

    master = options.get("master")
    if master is None:
        # probe a free port then RELEASE it: PADDLE_MASTER is the
        # jax.distributed coordinator address, and the coordinator service
        # binds it in rank 0 itself (same contract as the launch-CLI tests)
        from .store import TCPStore

        probe = TCPStore(is_master=True)
        master = f"127.0.0.1:{probe.port}"
        probe.close()

    ctx = multiprocessing.get_context("spawn")
    err_queue = ctx.Queue()
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(
            target=_worker,
            args=(func, tuple(args), rank, nprocs, master,
                  dict(options.get("env") or {}),
                  bool(options.get("init_env", True)), err_queue),
            daemon=daemon,
        )
        p.start()
        procs.append(p)

    context = MultiprocessContext(procs, err_queue)
    if join:
        context.join()
        return None
    return context
