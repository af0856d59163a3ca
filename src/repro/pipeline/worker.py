"""The multi-process actor plane: worker subprocesses for GIL-bound envs.

The thread plane (``ActorThread``) scales exactly as far as the emulator
releases the GIL: a C++ simulator stepped through a thin binding overlaps
fine, but a *Python-bound* emulator (ALE-style wrappers, pure-Python
simulators) serializes every replica's env stepping on one interpreter
lock — adding actors adds nothing (A3C and Stooke & Abbeel's accelerated
methods both reach for processes at this exact wall). This module is the
third execution plane: ``PipelineConfig.actor_backend = "process"`` puts
each actor replica in its own interpreter.

Topology (everything below the ``TrajectoryQueue`` is new; everything
above it — learner loop, V-trace update, ping-pong donation, metrics — is
untouched)::

    worker subprocess i                     parent process
    ───────────────────                     ──────────────
    spec.build() → private HostEnvPool      ProcessActorDrainer i (thread)
    jitted act_step (own compile)             ready_q.get() → wrap shm views
    loop: lease params ← ShmParamView         → Rollout → TrajectoryQueue
          free_q.get() → ShmStagingSet        (ActorBase quota/shutdown/
          collect_host(staging=set)            never-drop protocol, shared
          ready_q.put(set index)               verbatim with ActorThread)
                                            learner: get → update → commit
    params ← shm ping-pong slot  ◀──────────  (D2H publish once per update)

Wire protocol (per worker, all ``mp.Queue``):

* ``cmd_q``   parent→child: ``("run", quota, lockstep)`` | ``("stop",)``
* ``ready_q`` child→parent: ``("ready", platform)`` once, after setup
  (the JAX platform the child acts on — always ``"cpu"``), then per run
  ``("rollout", set_idx, seq, version)`` …
  then ``("spans", SpanEmitter.ship())`` — the child's telemetry ring
  (collect / lease / shm.copy / staging-wait spans, recorded child-side),
  merged parent-side under per-process trace track ``actor_id + 1`` —
  terminated by exactly one of ``("done", final_key)`` (quota finished —
  graceful checkout), ``("aborted",)`` (stop event honoured), or
  ``("error", traceback)`` (collection died; the drainer re-raises it so
  the stream hard-closes exactly like a crashed ``ActorThread``).
* ``free_q``  both ways: staging-set indices — the cross-process
  ``HostStagingRing`` lease. The parent seeds ``queue_depth + 2`` indices
  (the ring's sizing contract), the child acquires before writing, the
  learner's ``Rollout.release`` returns them after consuming.

One process per chip: a chip belongs to one process at a time, and the
parent learner holds it. So each child pins its JAX to the CPU platform as
its first act, before any array exists: workers act on the host CPU and
never initialise the accelerator.

Child lifecycle: workers are spawned once per ``PipelinedRL`` (spawn
context — fork would duplicate JAX runtime state) and persist across
``run()`` calls so re-runs don't pay the child's jit compile; they are
daemonic *and* poll ``multiprocessing.parent_process().is_alive()`` in
every blocking loop, so neither a clean parent exit nor a hard kill
leaves orphans stepping envs. A worker that dies silently (segfault, OOM
kill) is detected by its drainer's liveness poll and surfaced as the
actor error — EOF propagation without deadlock.
"""
from __future__ import annotations

import atexit
import multiprocessing as mp
import os
import queue as _stdlib_queue
import traceback
import weakref
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from repro.envs.host_env import HostEnvSpec
from repro.analysis import sanitize
from repro.pipeline.actor import ActorBase, Rollout, _copy_tree
from repro.pipeline.shm import ShmParamSlot, ShmStagingSet
from repro.telemetry.spans import (
    COLLECT,
    LEASE,
    QUEUE_PUT_WAIT,
    SHM_COPY,
    SpanEmitter,
)

__all__ = ["ProcessActorPlane", "ProcessActorDrainer"]


def _parent_alive() -> bool:
    p = mp.parent_process()
    return p is not None and p.is_alive()


def _orphan_unlink(sets, slot) -> None:
    """Child-side last resort for the shm estate: the parent normally owns
    every unlink, but a parent killed hard (SIGKILL) never runs its atexit
    reaper — the orphaned child destroys the segments on its way out so
    /dev/shm does not leak. POSIX unlink is safe under live mappings, and a
    sibling orphan racing us sees FileNotFoundError, which is success."""
    for s in sets or ():
        try:
            s.shm.unlink()
        except Exception:
            pass
    if slot is not None:
        for shm in getattr(slot, "_shms", ()) or ():
            try:
                shm.unlink()
            except Exception:
                pass


def _worker_main(spec: HostEnvSpec, arch_cfg, hp, slot_handle,
                 set_names: Sequence[str], key_host: np.ndarray,
                 cmd_q, ready_q, free_q, stop_evt, actor_id: int) -> None:
    """Child entry point: rebuild the env pool + acting step, then serve
    ``run`` commands until ``stop`` (or the parent disappears)."""
    import jax

    # first act, before any array exists: the parent holds the accelerator
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from repro.core.agents.paac import PAACAgent
    from repro.pipeline.actor import collect_host, make_host_act_step
    from repro.pipeline.shm import ShmParamView

    pool = sets = slot = None
    try:
        agent = PAACAgent(arch_cfg, hp)
        act_step = make_host_act_step(agent.act_fn())
        t_max = hp.t_max
        pool = spec.build()
        sets = [
            ShmStagingSet(t_max, spec.n_envs, spec.obs_shape, spec.obs_dtype,
                          name=n, create=False)
            for n in set_names
        ]
        # reader_id = this worker's slot: its param leases are attributable
        # (reserve-timeout diagnostics) and revocable (supervisor respawn)
        slot = ShmParamView(slot_handle, reader_id=actor_id)
        key = jnp.asarray(key_host)
        obs = pool.reset()
        # this worker's span track: recorded here (the spans describe *this*
        # process's blocking), shipped to the parent with the terminal
        # message of each run, merged into the run trace under pid
        # actor_id + 1
        em = SpanEmitter(f"worker{actor_id}")
    except Exception:
        # setup died (unbuildable env, shm attach failure): report it so the
        # first begin_run surfaces a traceback, not a bare dead child
        ready_q.put(("error", traceback.format_exc()))
        if pool is not None:
            pool.close()
        return
    ready_q.put(("ready", jax.devices()[0].platform))
    try:
        while True:
            try:
                cmd = cmd_q.get(timeout=1.0)
            except _stdlib_queue.Empty:
                if not _parent_alive():
                    # orphaned: the parent died without "stop" — and without
                    # its unlink duty (hard kill bypasses atexit)
                    _orphan_unlink(sets, slot)
                    return
                continue
            if cmd[0] == "stop":
                return
            # 4th element (absent pre-fault-plan): planned (after, mode)
            # kills this run executes in its own process
            _, quota, lockstep = cmd[0], cmd[1], cmd[2]
            faults = tuple(cmd[3]) if len(cmd) > 3 else ()
            try:
                aborted = False
                for seq in range(quota):
                    for after, mode in faults:
                        if after == seq:
                            if mode == "exit":
                                # the segfault/OOM-kill shape: no message,
                                # no traceback — the drainer's liveness
                                # poll must detect the silent death
                                os._exit(17)
                            raise RuntimeError(
                                f"FaultPlan: injected worker fault on actor "
                                f"{actor_id} after {seq} rollouts "
                                f"(mode={mode!r})"
                            )
                    if lockstep:
                        em.begin(LEASE)
                        while not slot.wait_for(seq, timeout=0.1):
                            if stop_evt.is_set() or not _parent_alive():
                                aborted = True
                                break
                        if aborted:  # abort mid-wait never counted as waiting
                            em.cancel()
                        else:
                            em.end()
                    if aborted or stop_evt.is_set():
                        aborted = True
                        break
                    # params lease is just the copy-out (inside read_params):
                    # the shm→host copy is the span, not a blocking wait
                    em.begin(SHM_COPY)
                    try:
                        params, version = slot.read_params()
                    finally:
                        em.end()
                    # cross-process staging lease: blocked here = the
                    # child-side backpressure stage (the parent hasn't
                    # recycled a set), this plane's queue.put_wait analog
                    em.begin(QUEUE_PUT_WAIT)
                    idx: Optional[int] = None
                    while idx is None:
                        try:
                            idx = free_q.get(timeout=0.1)
                        except _stdlib_queue.Empty:
                            if stop_evt.is_set() or not _parent_alive():
                                aborted = True
                                break
                    if aborted:
                        em.cancel()
                        break
                    em.end()
                    em.begin(COLLECT)
                    try:
                        obs, key, _traj, _last = collect_host(
                            act_step, pool, params, obs, key, t_max,
                            staging=sets[idx],
                        )
                    except Exception:
                        free_q.put(idx)  # don't leak the staging lease
                        raise
                    finally:
                        em.end()
                    ready_q.put(("rollout", idx, seq, version))
                ready_q.put(("spans", em.ship()))
                em.reset()  # a later run must not re-ship this run's spans
                if aborted:
                    ready_q.put(("aborted",))
                else:
                    ready_q.put(("done", np.asarray(key)))
            except Exception:
                # collection died (env crash, shm torn down, ...): report and
                # survive — the drainer turns this into the actor error and
                # the plane decides whether to reuse or stop us.
                tb = traceback.format_exc()
                try:
                    ready_q.put(("spans", em.ship()))
                    em.reset()
                except Exception:  # never mask the real failure
                    pass
                ready_q.put(("error", tb))
    finally:
        pool.close()
        for s in sets:
            s.close()
        slot.close()


class _WorkerHandle:
    """Parent-side bookkeeping for one spawned worker."""

    def __init__(self, actor_id: int, proc, cmd_q, ready_q, free_q, stop_evt,
                 sets: List[ShmStagingSet]):
        self.actor_id = actor_id
        self.proc = proc
        self.cmd_q = cmd_q
        self.ready_q = ready_q
        self.free_q = free_q
        self.stop_evt = stop_evt
        self.sets = sets  # parent-side views of the same shm blocks
        self.platform: Optional[str] = None  # from the child's "ready"


class ProcessActorDrainer(ActorBase):
    """Parent-side thread standing in for one worker subprocess.

    To everything above the plane split this *is* the actor replica: it
    honours ``ActorBase``'s quota/shutdown/never-drop protocol (checkout
    via ``producer_done``, hard ``close()`` on error) — it just sources
    payloads from its worker's ``ready_q`` instead of collecting them
    itself, wrapping the named shm staging set each descriptor points at
    into a zero-copy ``Rollout`` whose ``release`` returns the set index
    to the worker's free list.
    """

    def __init__(self, worker: _WorkerHandle, queue, telemetry=None,
                 actor_id: Optional[int] = None, ledger=None,
                 lockstep: bool = False):
        # actor_id can differ from the worker's slot: a respawned replica
        # gets a fresh epoch id while the child keeps its slot (which is
        # also its shm reader_id)
        super().__init__(
            queue, worker.actor_id if actor_id is None else actor_id,
            telemetry=telemetry)
        self._worker = worker
        self._telemetry = telemetry
        self.slot_index = worker.actor_id
        self._ledger = ledger
        self._lockstep = lockstep
        # seq offset for ledger-continuation runs: the child restarts its
        # local seq at 0 per run command, the stream must not
        self._seq_base = 0
        self.final_key: Optional[np.ndarray] = None

    def stop(self) -> None:
        super().stop()
        self._worker.stop_evt.set()  # reaches the child's blocking loops

    def _next_msg(self) -> Tuple:
        while True:
            try:
                return self._worker.ready_q.get(timeout=0.1)
            except _stdlib_queue.Empty:
                if not self._worker.proc.is_alive():
                    raise RuntimeError(
                        f"actor worker {self.slot_index} died without a "
                        f"message (exitcode "
                        f"{self._worker.proc.exitcode}) — envs or shm torn "
                        "down underneath it?"
                    ) from None

    def _produce(self) -> None:
        discard = False  # after stop/close: recycle sets, put nothing
        while True:
            msg = self._next_msg()
            kind = msg[0]
            if kind == "rollout":
                idx, seq, version = msg[1], msg[2], msg[3]
                free_q = self._worker.free_q
                if discard or self._stop_requested.is_set():
                    free_q.put(idx)  # keep the child's lease flowing
                    discard = True
                    continue
                s = self._worker.sets[idx]
                if not self._put(Rollout(
                    s.traj, s.last_obs, version, self.actor_id,
                    self._seq_base + seq,
                    release=(lambda i=idx: free_q.put(i)),
                )):
                    free_q.put(idx)
                    discard = True  # drain to the terminal message
                else:
                    self.produced += 1
                    if self._ledger is not None:
                        self._ledger.produced()
            elif kind == "ready":
                self._worker.platform = msg[1]
            elif kind == "spans":
                # the child's telemetry ring, shipped just before its
                # terminal message: give it a trace track of its own process
                if self._telemetry is not None:
                    self._telemetry.merge_shipped(
                        msg[1], pid=self.slot_index + 1
                    )
            elif kind == "done":
                self.final_key = msg[1]
                if self._ledger is not None and not discard \
                        and not self._stop_requested.is_set():
                    # quota done — a dead sibling may have orphaned more:
                    # claim it and send the idle child another run command
                    got = self._ledger.wait_for_work(
                        stop=self._stop_requested.is_set)
                    if got > 0:
                        extra = got + self._ledger.claim()
                        self._seq_base = self.produced
                        self.assigned += extra
                        self._worker.cmd_q.put(
                            ("run", int(extra), self._lockstep, ()))
                        continue
                return  # graceful checkout (ActorBase -> producer_done)
            elif kind == "aborted":
                return
            elif kind == "error":
                raise RuntimeError(
                    f"actor worker {self.slot_index} failed:\n{msg[1]}"
                )
            else:  # pragma: no cover - protocol violation
                raise RuntimeError(f"unknown worker message {msg!r}")


class _ShmSlotBridge:
    """Learner-facing twin of ``PingPongParamSlot`` for the process plane.

    ``reserve`` waits out the *cross-process* readers of shm buffer
    ``v % 2`` and hands back the device-side stale buffer (the fused
    step's donation target, exactly like the thread slot); ``commit``
    stores the published device copy and lands it in shared memory (the
    one D2H param copy per update that broadcasting to subprocesses
    costs). No in-process readers exist, so the device buffers need no
    reference counting.
    """

    def __init__(self, params: Any, shm_slot: ShmParamSlot, emitter=None):
        self._bufs = [_copy_tree(params), _copy_tree(params)]
        self._shm = shm_slot
        self._emitter = emitter  # learner-thread-only writer (no lock)

    def reserve(self, version: int, timeout: Optional[float] = None):
        if not self._shm.reserve(version, timeout=timeout):
            return None
        return self._bufs[version % 2]

    def holders(self, idx: int) -> List[str]:
        """Which workers still lease shm buffer ``idx`` (timeout naming)."""
        return self._shm.holders(idx)

    def commit(self, published: Any, version: int) -> None:
        self._bufs[version % 2] = published
        if self._emitter is not None:
            # the one per-update D2H param copy the process plane costs —
            # worth its own shm.copy span on the publish track; an intended
            # transfer edge, so it escapes the learner loop's guard scope
            self._emitter.begin(SHM_COPY)
            try:
                with sanitize.allowed("shm param publish"):
                    self._shm.commit(published, version)
            finally:
                self._emitter.end()
        else:
            with sanitize.allowed("shm param publish"):
                self._shm.commit(published, version)


class ProcessActorPlane:
    """Owner of the worker subprocesses and their shared-memory estate.

    Spawned once per ``PipelinedRL`` (process backend): allocates the
    param slot + per-worker staging sets, validates and ships each
    ``HostEnvSpec``, and keeps the children alive across ``run()`` calls.
    ``begin_run`` rebroadcasts the current params as version 0, hands each
    worker its quota, and returns the learner-side slot bridge plus one
    ``ProcessActorDrainer`` per worker; ``close`` is the orderly teardown
    (stop command, bounded join, terminate stragglers, unlink shm).
    """

    def __init__(self, specs: Sequence[HostEnvSpec], agent, queue_depth: int,
                 params: Any, keys: Sequence) -> None:
        if len(keys) != len(specs):
            raise ValueError("one RNG key per worker spec required")
        self._ctx = mp.get_context("spawn")
        self._slot = ShmParamSlot(params, self._ctx,
                                  max_readers=max(len(specs), 1))
        self._n_sets = queue_depth + 2  # the HostStagingRing sizing contract
        self._workers: List[_WorkerHandle] = []
        # retired handles of hard-killed workers: their staging sets may
        # still back in-flight payloads (and their free_q still receives
        # those payloads' release()s), so the estate is only torn down at
        # plane close, never at respawn time
        self._graveyard: List[_WorkerHandle] = []
        self._closed = False
        self._specs = list(specs)
        self._agent = agent
        self._initial_keys = [np.asarray(k) for k in keys]
        self._epochs = [0] * len(specs)  # respawn generation per slot
        _LIVE_PLANES.add(self)
        try:
            for i, spec in enumerate(specs):
                spec.validate_picklable()
                self._workers.append(self._spawn(i, self._initial_keys[i]))
        except BaseException:
            self.close()
            raise

    def _spawn(self, slot_idx: int, key_host: np.ndarray) -> _WorkerHandle:
        """Allocate one worker's estate (staging sets, queues, stop event)
        and start its process. The child's actor_id stays the *slot* index
        — it doubles as the shm param reader_id and trace track."""
        spec = self._specs[slot_idx]
        sets = [
            ShmStagingSet(self._agent.hp.t_max, spec.n_envs,
                          spec.obs_shape, spec.obs_dtype)
            for _ in range(self._n_sets)
        ]
        cmd_q = self._ctx.Queue()
        ready_q = self._ctx.Queue()
        free_q = self._ctx.Queue()
        for j in range(self._n_sets):
            free_q.put(j)
        stop_evt = self._ctx.Event()
        epoch = self._epochs[slot_idx]
        proc = self._ctx.Process(
            target=_worker_main,
            args=(spec, self._agent.cfg, self._agent.hp, self._slot.handle(),
                  [s.name for s in sets], key_host,
                  cmd_q, ready_q, free_q, stop_evt, slot_idx),
            name=(f"pipeline-worker-{slot_idx}" if epoch == 0
                  else f"pipeline-worker-{slot_idx}e{epoch}"),
            daemon=True,  # orphan reaping: die with the parent
        )
        proc.start()
        return _WorkerHandle(slot_idx, proc, cmd_q, ready_q, free_q,
                             stop_evt, sets)

    @property
    def n_workers(self) -> int:
        return len(self._workers)

    def worker_platforms(self) -> List[Optional[str]]:
        """The JAX platform each worker reported (``None`` until a run has
        drained its ``ready`` message)."""
        return [w.platform for w in self._workers]

    def begin_run(self, queue, quota: Sequence[int], lockstep: bool,
                  params: Any, telemetry=None, ledger=None, injector=None):
        """Start one ``run()``'s worth of collection on every worker.

        Returns ``(slot, drainers)`` with ``slot`` speaking the learner
        loop's reserve/commit protocol. The version counter rewinds to 0
        each run (workers are idle between runs, so no reader can hold a
        stale lease across the reset) — identical to the thread plane
        building a fresh ``PingPongParamSlot`` per run. With a ``telemetry``
        hub the drainers merge each worker's shipped span ring into it and
        the slot bridge spans its per-update D2H publish copy.
        """
        if self._closed:
            raise RuntimeError("begin_run() on a closed ProcessActorPlane")
        self._slot.publish(params, 0)
        drainers = []
        for w, q in zip(self._workers, quota):
            w.stop_evt.clear()
            faults = (injector.kills_for_worker(w.actor_id)
                      if injector is not None else ())
            w.cmd_q.put(("run", int(q), bool(lockstep), faults))
            d = ProcessActorDrainer(w, queue, telemetry=telemetry,
                                    ledger=ledger, lockstep=bool(lockstep))
            d.assigned = int(q)
            drainers.append(d)
        publish_em = (telemetry.emitter("shm.publish")
                      if telemetry is not None else None)
        return _ShmSlotBridge(params, self._slot, emitter=publish_em), drainers

    def respawn_worker(self, slot_idx: int, actor_id: int, quota: int,
                       lockstep: bool, queue, telemetry=None, ledger=None):
        """Stand a dead slot back up mid-run (supervisor path).

        Clears the dead replica's leaked param lease, then either reuses
        the still-alive child (an injected/in-child error leaves it parked
        at its command loop) or retires the handle to the graveyard and
        spawns a fresh process with a fresh shm estate and a fold_in-derived
        key (deterministic per (slot, epoch), never a key replay). Returns
        a started ``ProcessActorDrainer`` carrying the fresh epoch
        ``actor_id``; the caller starts it.
        """
        import jax

        if self._closed:
            raise RuntimeError("respawn_worker() on a closed plane")
        self._slot.revoke(slot_idx)
        self._epochs[slot_idx] += 1
        w = self._workers[slot_idx]
        if not w.proc.is_alive():
            w.proc.join(timeout=1.0)
            self._graveyard.append(w)
            key = np.asarray(jax.random.fold_in(
                jax.numpy.asarray(self._initial_keys[slot_idx]),
                self._epochs[slot_idx]))
            w = self._spawn(slot_idx, key)
            self._workers[slot_idx] = w
        w.stop_evt.clear()
        w.cmd_q.put(("run", int(quota), bool(lockstep), ()))
        d = ProcessActorDrainer(w, queue, telemetry=telemetry,
                                actor_id=actor_id, ledger=ledger,
                                lockstep=bool(lockstep))
        d.assigned = int(quota)
        return d

    def close(self, join_timeout: float = 10.0) -> None:
        """Stop workers (politely, then hard) and release the shm estate —
        including the graveyard of handles retired by respawns. Idempotent;
        safe to call with workers already dead."""
        if self._closed:
            return
        self._closed = True
        _LIVE_PLANES.discard(self)
        handles = self._workers + self._graveyard
        for w in handles:
            w.stop_evt.set()
            try:
                w.cmd_q.put(("stop",))
            except (ValueError, OSError):  # queue already torn down
                pass
        for w in handles:
            w.proc.join(timeout=join_timeout)
            if w.proc.is_alive():  # hung child: reap it hard
                w.proc.terminate()
                w.proc.join(timeout=join_timeout)
        for w in handles:
            for q in (w.cmd_q, w.ready_q, w.free_q):
                q.cancel_join_thread()
                q.close()
            for s in w.sets:
                s.close()
                s.unlink()
        self._slot.close()
        self._slot.unlink()


# Interpreter-exit reaper, replacing the old per-plane ``__del__``: CPython
# gives no ordering (or execution) guarantee for __del__ at shutdown — a
# plane caught in a reference cycle was torn down after the shm module's
# globals were cleared, or not at all, leaking /dev/shm segments and child
# processes. One atexit hook over a WeakSet runs while the interpreter is
# still whole; a plane closed normally has already removed itself.
_LIVE_PLANES: "weakref.WeakSet" = weakref.WeakSet()


def _reap_planes() -> None:  # pragma: no cover - exercised by test via call
    for plane in list(_LIVE_PLANES):
        try:
            plane.close(join_timeout=1.0)
        except Exception:
            pass


atexit.register(_reap_planes)
