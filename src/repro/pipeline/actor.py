"""The pipeline's acting half: rollout collection decoupled from learning.

Two collection paths, mirroring the two environment regimes of
``repro.core.framework``:

* ``make_collect_fn`` (re-exported from ``repro.core.rollout``) — JAX-native
  ``VectorEnv``: one jitted program collects a full ``t_max`` rollout whose
  output feeds the device plane (``DeviceTrajectoryRing``) without ever
  touching host memory.
* ``collect_host`` — ``HostEnvPool``: jitted batched acting interleaved with
  threaded host env stepping (paper §3's master/worker loop, run on the
  actor thread). While the env workers sleep in C/syscalls the GIL is
  released, so the learner's jitted update runs concurrently — this is the
  overlap that recovers the paper's Fig. 2 "50% env time". Trajectories are
  accumulated into reusable ``HostStagingRing`` buffers (one row-write per
  step into a preallocated ``(t_max, E, ...)`` set) instead of fresh numpy
  stacks per rollout.

``ParamSlot`` is the basic learner→actor exchange (a reference swap).
``PingPongParamSlot`` is its donation-safe upgrade: the learner's working
params are *never* handed to actors — each update publishes a bitwise copy
into one of two alternating actor-facing buffers, and actors bracket their
rollouts with ``acquire``/``release`` read leases so the learner can reclaim
(donate) the stale buffer only once nobody reads it. That is what makes
``donate_argnums`` on params *and* opt state safe in the learner step.

``Rollout`` is the queue payload: the trajectory, the bootstrap observation,
the behaviour params version (staleness = learner_version −
behaviour_version), and an optional host-side ``release`` callback the
learner invokes once the payload is fully consumed (returns a staging set to
its ring; ``None`` on the device plane, where XLA's donation chain recycles
the buffers instead).
"""
from __future__ import annotations

import threading
from queue import Full
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rollout import Transition, make_collect_fn  # noqa: F401
from repro.analysis.lockcheck import make_condition, make_lock
from repro.pipeline.queue import QueueClosed
from repro.telemetry.spans import (
    COLLECT,
    LEASE,
    QUEUE_PUT_WAIT,
    SpanEmitter,
)

__all__ = [
    "ParamSlot",
    "PingPongParamSlot",
    "HostStagingRing",
    "StagingSet",
    "Rollout",
    "ActorBase",
    "ActorThread",
    "collect_host",
    "make_collect_fn",
]


class ParamSlot:
    """Versioned single-slot param exchange (learner → actor).

    The learner ``publish``es params after every update; the actor ``read``s
    whatever is newest when it starts a rollout. ``wait_for`` lets a
    lock-stepped actor block until the learner has caught up — synchronous
    semantics through the pipelined code path.

    ``acquire``/``release`` are the lease hooks actors use so the slot's
    donation-safe subclass can track outstanding readers; here they are a
    plain ``read`` and a no-op (reference-swapped params are never reclaimed,
    so holding them needs no protection).
    """

    def __init__(self, params: Any, version: int = 0):
        self._params = params
        self._version = version
        self._cond = make_condition("param_slot.cond")

    def publish(self, params: Any, version: int) -> None:
        with self._cond:
            self._params = params
            self._version = version
            self._cond.notify_all()

    def read(self) -> Tuple[Any, int]:
        with self._cond:
            return self._params, self._version

    def acquire(self, holder: Optional[str] = None) -> Tuple[Any, int]:
        """Take a read lease on the newest params (paired with ``release``).
        ``holder`` labels the leasing party for timeout diagnostics."""
        return self.read()

    def release(self, version: int, holder: Optional[str] = None) -> None:
        """Return the lease taken by ``acquire`` (no-op for the base slot)."""

    def wait_for(self, version: int, timeout: Optional[float] = None) -> bool:
        with self._cond:
            return self._cond.wait_for(
                lambda: self._version >= version, timeout=timeout
            )

    @property
    def version(self) -> int:
        with self._cond:
            return self._version


def _copy_tree(tree):
    return jax.tree_util.tree_map(lambda a: a.copy(), tree)


class PingPongParamSlot(ParamSlot):
    """Two alternating actor-facing param buffers with read leases.

    The donation problem: if the learner jit donates its params, the buffers
    an actor snapshotted via ``read()`` are deleted by the *next* update —
    a use-after-free racing every in-flight rollout. The fix is to never
    share the learner's working params at all: ``publish`` of version ``v``
    lands a bitwise copy in buffer ``v % 2``, actors lease the newest buffer
    for exactly the duration of one rollout, and the learner ``reserve``s a
    buffer for reuse only after its last reader released. The stale buffer is
    handed into the fused learner step as a donation target, so on backends
    that realize input/output aliasing the publish copy writes straight over
    it — classic ping-pong double buffering, one param-copy per update, zero
    steady-state allocation.

    Lease protocol (actor side)::

        params, version = slot.acquire()   # readers[v % 2] += 1
        try:  ... collect with params ...
        finally: slot.release(version)     # readers[v % 2] -= 1

    Publish protocol (learner side, per update ``v``)::

        dst = slot.reserve(v)        # blocks until readers[v % 2] == 0
        ... fused jitted step consumes dst (donated) and returns `published`
        slot.commit(published, v)    # buffer v % 2 <- published, notify

    ``reserve`` can only wait on a reader that is mid-rollout — actors
    release before blocking on the queue — so the wait is bounded by one
    collect and cannot deadlock.
    """

    def __init__(self, params: Any, version: int = 0):
        # actors only ever see copies; the caller keeps the original as the
        # learner's private working params (safe to donate from step one)
        bufs = [_copy_tree(params), _copy_tree(params)]
        super().__init__(bufs[version % 2], version)
        self._bufs = bufs
        self._readers = [0, 0]
        # per-buffer holder labels, parallel to _readers: when a reserve
        # times out, the error can name *who* never released (the stall
        # watchdog's stage-naming idiom applied to leases)
        self._holders: dict = {0: [], 1: []}

    def acquire(self, holder: Optional[str] = None) -> Tuple[Any, int]:
        with self._cond:
            idx = self._version % 2
            self._readers[idx] += 1
            if holder is not None:
                self._holders[idx].append(holder)
            return self._params, self._version

    def release(self, version: int, holder: Optional[str] = None) -> None:
        with self._cond:
            idx = version % 2
            self._readers[idx] -= 1
            assert self._readers[idx] >= 0, "unbalanced release"
            if holder is not None:
                try:
                    self._holders[idx].remove(holder)
                except ValueError:
                    pass  # unlabeled acquire / already revoked
            self._cond.notify_all()

    def holders(self, idx: int) -> List[str]:
        """Labels of the parties currently leasing buffer ``idx``."""
        with self._cond:
            return list(self._holders[idx])

    def revoke(self, holder: str) -> int:
        """Drop every lease ``holder`` still holds (supervisor path: a
        replica that died without releasing). Returns leases cleared."""
        cleared = 0
        with self._cond:
            for idx in (0, 1):
                while holder in self._holders[idx]:
                    self._holders[idx].remove(holder)
                    self._readers[idx] -= 1
                    cleared += 1
            if cleared:
                self._cond.notify_all()
        return cleared

    def reserve(self, version: int, timeout: Optional[float] = None):
        """Claim buffer ``version % 2`` for the upcoming publish.

        Blocks until every reader of the buffer's previous contents has
        released, then returns the stale param tree — the donation target
        for the fused learner step. Returns ``None`` on timeout.
        """
        idx = version % 2
        with self._cond:
            if not self._cond.wait_for(
                lambda: self._readers[idx] == 0, timeout=timeout
            ):
                return None
            return self._bufs[idx]

    def commit(self, params: Any, version: int) -> None:
        """Install the published copy produced against ``reserve``'s target."""
        idx = version % 2
        with self._cond:
            assert self._readers[idx] == 0, "commit while buffer leased"
            self._bufs[idx] = params
            self._params = params
            self._version = version
            self._cond.notify_all()

    def publish(self, params: Any, version: int,
                timeout: Optional[float] = 60.0) -> None:
        """Unfused publish: copy ``params`` into the alternating buffer.

        Convenience path (used when the learner step was not built with
        ``fused_publish``): blocks for the buffer's readers, copies, commits.
        A reserve timeout means a reader never released its lease — raise
        loudly rather than fall through to ``commit`` on a still-leased
        buffer (which would hand actors a tree mutating under them)."""
        dst = self.reserve(version, timeout=timeout)
        if dst is None:
            held = ", ".join(self.holders(version % 2)) or "an unlabeled party"
            raise RuntimeError(
                f"PingPongParamSlot.publish(version={version}): reserve "
                f"timed out after {timeout}s — buffer {version % 2} is "
                f"still leased by {held} (died without release()?)"
            )
        assert dst is self._bufs[version % 2], (
            "reserve() returned a tree that is not the reserved buffer"
        )
        self.commit(_copy_tree(params), version)


class Rollout(NamedTuple):
    """Queue payload: one collected rollout plus its provenance.

    ``actor_id``/``seq`` tag which replica produced the rollout and where it
    sits in that replica's stream — the learner uses them to attribute
    staleness and idle time per actor, and the pipeline tests to prove every
    ``(actor_id, seq)`` is learned from exactly once. ``release`` (host plane
    only) returns the payload's staging buffers to their ring once the
    learner has fully consumed the update."""

    traj: Transition  # time-major (T, E, ...)
    last_obs: jnp.ndarray  # (E, *obs_shape) — bootstrap observation
    behavior_version: int  # params version the actor acted with
    actor_id: int = 0  # which actor replica collected it
    seq: int = 0  # per-actor rollout sequence number
    release: Optional[Callable[[], None]] = None  # staging-set return hook


# ---------------------------------------------------------------------------
# Host staging — reusable pinned buffers for host-plane payloads
# ---------------------------------------------------------------------------


def staging_fields(t_max: int, n_envs: int, obs_shape: Tuple[int, ...],
                   obs_dtype) -> List[Tuple[Tuple[int, ...], np.dtype]]:
    """The canonical staging-payload layout: ``Transition``'s six fields (in
    field order) followed by the bootstrap ``last_obs``. Both staging
    backends build from this one list — ``StagingSet`` as process-private
    numpy arrays, ``repro.pipeline.shm.ShmStagingSet`` as views into one
    shared-memory block — so the layouts cannot drift apart."""
    E = n_envs
    obs_shape = tuple(obs_shape)
    obs_dtype = np.dtype(obs_dtype)
    return [
        ((t_max, E) + obs_shape, obs_dtype),      # Transition.obs
        ((t_max, E), np.dtype(np.int32)),         # Transition.action
        ((t_max, E), np.dtype(np.float32)),       # Transition.reward
        ((t_max, E), np.dtype(bool)),             # Transition.done
        ((t_max, E), np.dtype(np.float32)),       # Transition.value
        ((t_max, E), np.dtype(np.float32)),       # Transition.logp
        ((E,) + obs_shape, obs_dtype),            # last_obs
    ]


class StagingSet:
    """One reusable host payload: a ``(t_max, E, ...)`` trajectory plus the
    bootstrap observation, written in place row by row during collection."""

    __slots__ = ("traj", "last_obs")

    def __init__(self, t_max: int, n_envs: int, obs_shape: Tuple[int, ...],
                 obs_dtype):
        arrays = [np.zeros(shape, dtype) for shape, dtype in
                  staging_fields(t_max, n_envs, obs_shape, obs_dtype)]
        self.traj = Transition(*arrays[:6])
        self.last_obs = arrays[6]


class HostStagingRing:
    """Pool of reusable staging sets for one actor's host-plane rollouts.

    Replaces the per-rollout ``np.stack`` of per-step copies with writes into
    preallocated buffers: ``acquire`` hands out a free set, the payload's
    ``release`` callback (invoked by the learner after it has consumed the
    update, i.e. after the H2D transfer is provably complete) returns it.
    ``n_sets`` must cover every set simultaneously in flight: up to
    ``queue_depth`` enqueued + 1 consumed-but-unreleased + 1 being written,
    so callers size it ``queue_depth + 2``. ``acquire`` never blocks when
    that invariant holds; a blocked acquire is a release-protocol bug, which
    the timeout turns into a loud error instead of a hang.
    """

    def __init__(self, n_sets: int, t_max: int, n_envs: int,
                 obs_shape: Tuple[int, ...], obs_dtype=np.float32):
        if n_sets < 2:
            raise ValueError(f"staging ring needs >= 2 sets, got {n_sets}")
        self._free: List[StagingSet] = [
            StagingSet(t_max, n_envs, obs_shape, obs_dtype)
            for _ in range(n_sets)
        ]
        self.n_sets = n_sets
        self._cond = make_condition("staging_ring.cond")

    def acquire(self, timeout: float = 60.0) -> StagingSet:
        with self._cond:
            if not self._cond.wait_for(lambda: self._free, timeout=timeout):
                raise RuntimeError(
                    "HostStagingRing.acquire timed out — a payload was "
                    "consumed without its release() being called"
                )
            return self._free.pop()

    def release(self, s: StagingSet) -> None:
        with self._cond:
            self._free.append(s)
            self._cond.notify_all()

    def free_sets(self) -> int:
        with self._cond:
            return len(self._free)


def make_host_act_step(act_fn: Callable) -> Callable:
    """Fuse one acting step — forward, sample, behaviour logp — into a
    single jitted program so the host loop pays one dispatch per step."""

    @jax.jit
    def act_step(params, obs, key):
        key, k_act = jax.random.split(key)
        logits, value = act_fn(params, obs)
        action = jax.random.categorical(k_act, logits)
        # behaviour log-prob from the sampled action's logit alone (same
        # gather as core/rollout.step): log π(a|s) = logits[a] − logsumexp.
        # Gathering first keeps the per-step dispatch from materializing the
        # full (E, A) log_softmax matrix when one column per row is read.
        action_logit = jnp.take_along_axis(logits, action[:, None], axis=1)[:, 0]
        logp = action_logit - jax.scipy.special.logsumexp(logits, axis=1)
        return action, value, logp, key

    return act_step


def collect_host(act_step: Callable, pool, params, obs, key, t_max: int,
                 staging: Optional[StagingSet] = None):
    """Collect ``t_max`` steps from a ``HostEnvPool`` (paper §3 loop).

    ``act_step`` is the jitted fused acting step (``make_host_act_step``);
    env stepping runs on the pool's worker threads. Returns
    ``(next_obs, key, traj, last_obs)`` with ``traj`` a time-major
    ``Transition`` of *host* (numpy) arrays — including the behaviour
    log-prob the learner's importance correction needs — transferred to the
    device only when the learner dispatches its update.

    With ``staging`` (a ``HostStagingRing`` set) every step writes its row
    directly into the set's preallocated buffers — zero numpy allocation per
    rollout — and the returned ``traj``/``last_obs`` *are* the staging
    arrays: the caller must not reuse the set until the learner has consumed
    the payload (the pipeline's ``Rollout.release`` protocol). Without
    ``staging`` each call allocates fresh arrays (safe for one-shot callers
    like benchmarks).
    """
    # accumulate on the host (numpy): the only device traffic per step is the
    # fused act_step — extra device ops here would queue behind the learner's
    # update and stretch the rollout. The trajectory stays host-side; the
    # H2D transfer happens when the learner dispatches its update.
    if staging is None:
        staging = StagingSet(t_max, pool.n_envs, pool.obs_shape,
                              np.asarray(obs).dtype)
    traj, last = staging.traj, staging.last_obs
    np.copyto(last, np.asarray(obs))
    for t in range(t_max):
        traj.obs[t] = last
        action, value, logp, key = act_step(params, traj.obs[t], key)
        action_np = np.asarray(action)
        next_obs, reward, done = pool.step_host(action_np)
        traj.action[t] = action_np
        traj.reward[t] = reward
        traj.done[t] = done
        traj.value[t] = np.asarray(value)
        traj.logp[t] = np.asarray(logp)
        np.copyto(last, next_obs)
    return last, key, traj, last  # final obs is the bootstrap observation


class ActorBase(threading.Thread):
    """Shared replica protocol for both actor backends (thread & process).

    The contract every replica honours, independent of *where* its rollouts
    are produced (in this thread, or in a worker subprocess this thread
    drains):

    * **quota** — produce exactly ``iterations`` payloads (possibly zero:
      a replica handed quota 0 by an ``iterations < num_actors`` run goes
      straight to checkout),
    * **never-drop** — every produced payload is ``_put`` into the shared
      stream, which blocks (backpressure) rather than discards,
    * **shutdown** — finishing the quota (or being ``stop()``ed, or finding
      the stream closed underneath) checks out via ``producer_done()``; the
      stream closes only after the *last* replica checks out. A replica
      that dies records its exception and hard-``close()``s the stream so
      the learner and sibling replicas unwind promptly instead of
      deadlocking.

    Subclasses implement ``_produce()`` (the body between start and
    checkout); the base class owns ``_put``, ``stop`` and the
    error-vs-checkout epilogue.
    """

    def __init__(self, queue, actor_id: int = 0, telemetry=None):
        super().__init__(name=f"pipeline-actor-{actor_id}", daemon=True)
        self._queue = queue
        self.actor_id = actor_id
        self._stop_requested = threading.Event()
        # this replica's span track (single-writer: only this thread records).
        # wait_s/put_wait_s are *derived* from its per-category totals — the
        # same float accumulation the old ad-hoc counters performed.
        if telemetry is not None:
            self.span_emitter = telemetry.emitter(f"actor{actor_id}")
        else:
            self.span_emitter = SpanEmitter(f"actor{actor_id}")
        self.error: Optional[BaseException] = None
        # fault-tolerance surface (repro.pipeline.supervisor): the slot this
        # replica occupies (stable across respawns, unlike actor_id), its
        # quota accounting, and the supervisor consulted by the epilogue. A
        # handled fault leaves ``error`` set (diagnostics) but marks
        # ``fault_handled`` so the run doesn't treat it as fatal.
        self.slot_index = actor_id
        self.assigned = 0  # payloads this replica must produce
        self.produced = 0  # payloads successfully put so far
        self.supervisor = None
        self.fault_handled = False

    @property
    def wait_s(self) -> float:
        """Time blocked waiting for params (lockstep) — span-derived."""
        return self.span_emitter.total(LEASE)

    @property
    def put_wait_s(self) -> float:
        """Time blocked in queue.put (backpressure) — span-derived."""
        return self.span_emitter.total(QUEUE_PUT_WAIT)

    def stop(self) -> None:
        """Ask the actor to exit at its next blocking point (learner died)."""
        self._stop_requested.set()

    # hot-path
    def _put(self, rollout: Rollout) -> bool:
        """Bounded put, interruptible by stop()/close(). Returns False when
        the actor should exit instead of producing more."""
        self.span_emitter.begin(QUEUE_PUT_WAIT)
        try:
            while True:
                try:
                    self._queue.put(rollout, timeout=0.1)
                    return True
                except Full:
                    if self._stop_requested.is_set():
                        return False
                except QueueClosed:
                    return False  # stream aborted under us — not our error
        finally:
            self.span_emitter.end()

    def _produce(self) -> None:
        raise NotImplementedError

    def run(self) -> None:
        try:
            self._produce()
        except BaseException as e:  # surfaced by the learner loop
            self.error = e
        finally:
            if self.error is not None:
                # with a supervisor, the dying thread *is* the recovery
                # context: on_actor_error respawns a replacement (which
                # inherits this replica's producer slot) or degrades by
                # orphaning the remaining quota (checking the slot out
                # itself). Only an unhandled death hard-aborts the stream —
                # exactly the pre-supervisor fail-fast path.
                sup = self.supervisor
                if sup is not None and sup.on_actor_error(self):
                    self.fault_handled = True
                else:
                    self._queue.close()  # abort: wake learner + siblings
            else:
                self._queue.producer_done()


class ActorThread(ActorBase):
    """One in-process actor replica: collects ``iterations`` rollouts on its
    own thread and feeds the shared trajectory queue (host plane) or device
    ring (device plane).

    ``collect(params, key) -> (key, traj, last_obs, release)`` encapsulates
    either collection path with env state captured in the closure; the
    thread owns the acting RNG key, and ``release`` (or ``None``) rides the
    payload so the learner can return staging buffers. Params are taken
    under an ``acquire``/``release`` lease for exactly the duration of the
    collect — never while blocked on the queue — which is what lets a
    ping-pong slot reclaim stale buffers without racing this thread. In
    ``lockstep`` mode the actor waits until the learner has published
    version i before collecting rollout i (so data is never stale);
    otherwise it reads the freshest available params and runs ahead up to
    the queue depth (shared across all replicas).

    Quota/shutdown semantics are ``ActorBase``'s; its process-backend twin
    (``repro.pipeline.worker.ProcessActorDrainer``) shares them verbatim.
    """

    def __init__(self, collect: Callable, queue, slot: ParamSlot, key,
                 iterations: int, lockstep: bool = False, actor_id: int = 0,
                 telemetry=None, slot_index: Optional[int] = None,
                 start_seq: int = 0, ledger=None, injector=None,
                 snapshot: Optional[Callable] = None):
        super().__init__(queue, actor_id, telemetry=telemetry)
        self._collect = collect
        self._slot = slot
        self._key = key
        self.assigned = iterations
        self._lockstep = lockstep
        self.slot_index = actor_id if slot_index is None else slot_index
        # seq offset for resumed runs: local rollout index i is tagged
        # ``start_seq + i`` so the (actor_id, seq) stream stays continuous
        # with the pre-checkpoint run
        self._start_seq = start_seq
        # quota ledger (supervisor runs): lets this replica pick up a dead
        # sibling's orphaned quota after finishing its own
        self._ledger = ledger
        # deterministic fault injection (FaultPlan), None outside tests
        self._injector = injector
        # checkpoint support: snapshot(key) -> opaque resume state captured
        # after each collect; the learner calls consume_state(seq) as it
        # consumes the matching payload, so the log holds at most the
        # in-flight window (queue depth + 1) of entries
        self._snapshot = snapshot
        self._state_log: dict = {}
        self._state_lock = make_lock("actor.state")

    @property
    def key(self):
        """This replica's RNG key as of its latest rollout."""
        return self._key

    def consume_state(self, seq: int):
        """Pop (and prune up to) the resume state recorded after rollout
        ``seq``; ``None`` when snapshotting is off or seq predates it."""
        with self._state_lock:
            st = self._state_log.get(seq)
            for k in [k for k in self._state_log if k <= seq]:
                del self._state_log[k]
            return st

    def _produce(self) -> None:
        i = 0  # local rollout index (lockstep waits on it; seq offsets it)
        while True:
            if i >= self.assigned:
                if self._ledger is None:
                    return
                # quota done — but a sibling may have died with quota
                # outstanding: block for orphaned work instead of checking
                # out, until the ledger proves no work can remain
                got = self._ledger.wait_for_work(
                    stop=self._stop_requested.is_set)
                if got <= 0:
                    return
                self.assigned += got
                continue
            if self._injector is not None:
                self._injector.maybe_kill(self.slot_index, self.produced)
                self._injector.lease_delay(self.slot_index, i)
            if self._lockstep:
                # lease span: the stop-abort path cancels instead of ending
                # (the pre-telemetry counter never accumulated it either)
                self.span_emitter.begin(LEASE)
                while not self._slot.wait_for(i, timeout=0.1):
                    if self._stop_requested.is_set():
                        self.span_emitter.cancel()
                        return
                self.span_emitter.end()
            if self._stop_requested.is_set():
                return
            # lease the params only for the collect: released before the
            # (potentially long) blocking put so the learner's reserve()
            # wait is bounded by one rollout. The instant acquire() itself is
            # deliberately unspanned: wait_s means *blocked on the learner*.
            params, version = self._slot.acquire(holder=self.name)
            self.span_emitter.begin(COLLECT)
            try:
                self._key, traj, last_obs, release = self._collect(
                    params, self._key
                )
            finally:
                self.span_emitter.end()
                self._slot.release(version, holder=self.name)
            seq = self._start_seq + i
            if self._snapshot is not None:
                # capture post-rollout state *before* the put: by the time
                # the learner can consume seq, its resume state exists
                with self._state_lock:
                    self._state_log[seq] = self._snapshot(self._key)
            if not self._put(
                Rollout(traj, last_obs, version, self.actor_id, seq, release)
            ):
                return
            self.produced += 1
            if self._ledger is not None:
                self._ledger.produced()
            i += 1
