"""``PipelinedRL`` — the asynchronous multi-actor/learner backend.

Drop-in alternative to ``repro.core.ParallelRL`` (same constructor shape,
same ``run(iterations) -> RunResult``) that splits Algorithm 1 across
``num_actors`` actor threads and one learner thread joined by a bounded
trajectory stream:

    actor thread i: lease latest params → collect rollout → put
    learner thread: get → fused (V-trace update + publish) → commit params

The stream runs on one of two *queue planes* (``PipelineConfig.
rollout_plane``): the device-resident ``DeviceTrajectoryRing`` for
JAX-native envs — trajectories never leave the accelerator, and ``get()``
hands each slot to the learner with sole ownership so its memory is
reclaimed the moment the update consumes it — or the host ``TrajectoryQueue``
for ``HostEnvPool``, whose rollouts are born in host memory and ride
reusable ``HostStagingRing`` buffers (returned to their ring by the
payload's ``release`` callback once the learner has consumed the update).

Params flow the other way through a ``PingPongParamSlot``: the learner's
working params and opt state are private (and therefore donated — the
update runs alloc-free in steady state), while each update publishes a
bitwise snapshot into one of two alternating actor-facing buffers inside
the same fused dispatch. Actors lease a snapshot for exactly one rollout;
the learner reuses a stale buffer only after its last reader released.

Orthogonal to the queue plane is the *actor backend* (``PipelineConfig.
actor_backend``): ``"thread"`` replicas are ``ActorThread``s in this
process (fine whenever env stepping releases the GIL), while ``"process"``
moves each replica into a worker subprocess (``repro.pipeline.worker``) —
the only backend that scales GIL-holding Python emulators. Process workers
rebuild their env pools from picklable ``HostEnvSpec`` recipes, collect
into ``multiprocessing.shared_memory`` staging sets, and are drained by
parent-side ``ProcessActorDrainer`` threads into the same
``TrajectoryQueue``; params broadcast worker-ward through a shared-memory
ping-pong slot speaking the same reserve/commit protocol. The learner loop
below the ``run()`` plane split is byte-for-byte shared between backends.

Each actor replica owns a private slice of the environments: a single env is
split along the env axis (``HostEnvPool.shard`` for external pools,
``narrow_vector_env`` for JAX-native envs, ``HostEnvSpec.shard`` for
process workers), or a list of envs gives each replica its own full pool
(GA3C's n_actors sweep — more emulators hide more env latency). With queue
depth d the actors collectively run at most d
rollouts ahead; staleness is bounded by the depth and corrected by the
learner's full V-trace targets (``PipelineConfig.rho_bar`` / ``c_bar``). In
``lockstep`` mode (single actor) the actor always waits for fresh params and
the pipeline reproduces the synchronous trajectory stream exactly — bitwise,
on either plane, when the clips are infinite.

The win is wall-clock overlap: on the ``HostEnvPool`` path the env workers
hold no GIL while stepping, so N actors' env latencies, their jitted acting
steps, and the learner's jitted update all run concurrently — the paper's
Fig. 2 "50% env time" recovered, and scaled past what one actor can hide.
On the device plane the win is the removed host round trip plus full
donation: one fused dispatch per iteration, no staging copies, no
steady-state allocation (``benchmarks/fig2_time_split.run_device_ring``).

A third stream variant is the *replay plane* (``PipelineConfig.
replay_plane``): the FIFO ring is swapped for a sampled ``ReplayRing`` —
actors never block (a full ring evicts its oldest rollout), each update
*samples* ``replay_batch`` retained rollouts, and the learner step is
either DQN's replay-fed TD update (``repro.pipeline.offpolicy``) or the
same V-trace PAAC step consuming rollouts whose staleness the clips
correct. The run() loop below is unchanged: the ring speaks the queue
surface (one ``get()`` per fresh rollout ticket), and ``_apply_update``
hides which learner-private state rides the update signature.
"""
from __future__ import annotations

import queue as _stdlib_queue
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize
from repro.analysis.lockcheck import locks_enabled, monitor
from repro.checkpoint.checkpointer import (
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.configs.base import PipelineConfig
from repro.core.framework import MetricsAccumulator, RunResult, init_rl_common
from repro.core.rollout import make_collect_fn
from repro.envs.base import narrow_vector_env
from repro.envs.host_env import HostEnvPool, HostEnvShard, HostEnvSpec
from repro.pipeline.actor import (
    ActorThread,
    HostStagingRing,
    PingPongParamSlot,
    Rollout,
    collect_host,
)
from repro.pipeline.faults import FaultInjector, FaultPlan
from repro.pipeline.learner import make_learner_step, make_sharded_learner_step
from repro.pipeline.queue import CLOSED, TrajectoryQueue
from repro.pipeline.ring import DeviceTrajectoryRing, MeshTrajectoryRing
from repro.pipeline.supervisor import ActorSupervisor, QuotaLedger
from repro.telemetry import (
    LEARNER_UPDATE,
    LEASE,
    PUBLISH,
    QUEUE_GET_WAIT,
    Telemetry,
)
from repro.utils import get_logger

log = get_logger("pipeline")


def _device_view(tree, device):
    """Zero-copy single-device view of a mesh-replicated param tree.

    A fully-replicated global array holds one shard per mesh device;
    ``addressable_shards[i].data`` *is* the device-local array backing that
    shard — no copy, no host round trip. Actor lane ``i`` extracts its view
    under the ping-pong read lease, feeds its single-device collect, and
    drops it before release, so the learner's donation of the stale buffer
    can never race a live view (same invariant as the flat device plane).
    """
    def leaf(l):
        for s in l.addressable_shards:
            if s.device == device:
                return s.data
        raise RuntimeError(
            f"replicated param leaf has no shard on {device} — params are "
            "not placed on the rollout mesh"
        )

    return jax.tree_util.tree_map(leaf, tree)


class PipelinedRL:
    """Asynchronous multi-actor/learner pipeline over the PAAC framework."""

    def __init__(
        self,
        env,
        agent,
        *,
        optimizer: str = "rmsprop",
        lr_schedule: Optional[Callable] = None,
        seed: int = 0,
        pipeline: PipelineConfig = PipelineConfig(),
    ):
        from repro.core.agents.dqn import DQNAgent
        from repro.core.agents.paac import PAACAgent

        # exact types: subclasses (LaggedPAACAgent) and look-alikes (PPOAgent)
        # carry their own loss/state that make_learner_step would silently
        # drop. DQNAgent rides only the replay plane (its learner step is the
        # replay-fed TD update, not V-trace).
        self._replay = pipeline.replay_plane
        self._dqn = type(agent) is DQNAgent
        if self._dqn and not self._replay:
            raise ValueError(
                "DQNAgent needs the replay plane: pass PipelineConfig("
                "replay_plane=True) — the FIFO planes feed the on-policy "
                "V-trace learner"
            )
        if not self._dqn and type(agent) is not PAACAgent:
            raise NotImplementedError(
                f"PipelinedRL drives plain PAACAgent (got {type(agent).__name__}) "
                "on the FIFO planes, plus DQNAgent on the replay plane; other "
                "agents carry losses the learner steps would silently drop"
            )
        n_actors = pipeline.num_actors
        if n_actors < 1:
            raise ValueError(f"num_actors must be >= 1, got {n_actors}")
        # the mesh plane runs one actor lane per mesh device: num_actors is
        # normalized to mesh_shape (PipelineConfig rejects anything else)
        self._want_mesh = pipeline.rollout_plane == "mesh" or (
            pipeline.rollout_plane == "auto" and pipeline.mesh_shape > 1
        )
        if self._want_mesh:
            if pipeline.num_actors not in (1, pipeline.mesh_shape):
                raise ValueError(
                    "the mesh plane runs exactly one actor lane per mesh "
                    f"device: num_actors must be 1 (auto) or mesh_shape="
                    f"{pipeline.mesh_shape}, got {pipeline.num_actors}"
                )
            n_actors = pipeline.mesh_shape
        if pipeline.lockstep and n_actors > 1 and not self._want_mesh:
            raise ValueError(
                "lockstep (synchronous semantics) requires num_actors == 1 "
                "(or the mesh plane, whose lanes are consumed in lockstep "
                "sets — one sub-rollout per lane per update)"
            )
        self._backend = pipeline.actor_backend
        if self._backend not in ("thread", "process"):
            raise ValueError(
                "actor_backend must be 'thread' or 'process', got "
                f"{pipeline.actor_backend!r}"
            )
        self._owned_pools: List = []  # pools built here from HostEnvSpec
        self._process_plane = None
        # thread backend accepts HostEnvSpec as sugar: build the pool(s)
        # here (and own their close()) so everything downstream is uniform
        if self._backend == "thread":
            if isinstance(env, HostEnvSpec):
                env = env.build()
                self._owned_pools.append(env)
            elif isinstance(env, (list, tuple)) and any(
                isinstance(e, HostEnvSpec) for e in env
            ):
                env = [e.build() if isinstance(e, HostEnvSpec) else e
                       for e in env]
                self._owned_pools.extend(
                    e for e in env if isinstance(e, HostEnvPool))
        if isinstance(env, (list, tuple)):
            if len(env) != n_actors:
                raise ValueError(
                    f"got {len(env)} per-actor envs for num_actors={n_actors}"
                )
            per_actor_envs: Optional[List] = list(env)
            env = per_actor_envs[0]
        else:
            per_actor_envs = None
        self.env = env
        self.agent = agent
        self.pipeline = pipeline
        if self._backend == "process":
            # the process plane rebuilds env pools inside worker subprocesses
            # from picklable specs — live pools can't cross the boundary
            if not isinstance(env, HostEnvSpec) or any(
                not isinstance(e, HostEnvSpec)
                for e in (per_actor_envs or [])
            ):
                raise ValueError(
                    "actor_backend='process' requires a HostEnvSpec (or a "
                    "per-actor list of them): worker subprocesses rebuild "
                    "their env pools from the picklable spec — a live "
                    f"{type(env).__name__} cannot be shipped to a child"
                )
            if per_actor_envs is not None:
                if any(e.n_envs != env.n_envs for e in per_actor_envs):
                    raise ValueError("per-actor specs must have equal n_envs")
                self._proc_specs = list(per_actor_envs)
            else:
                self._proc_specs = (env.shard(n_actors) if n_actors > 1
                                    else [env])
            self._host = True  # process rollouts are born in host shm
        else:
            self._proc_specs = None
            self._host = hasattr(env, "step_host")
        self._n_actors = n_actors  # mesh plane: one lane per mesh device
        self._seed = seed  # the ReplayRing's sample stream seed
        self._plane = self._resolve_plane(pipeline.rollout_plane)
        if self._replay and self._plane != "device":
            raise ValueError(
                "replay_plane requires a JAX-native env on the device plane: "
                "the ReplayRing retains sampled rollouts on the accelerator, "
                "which host-born payloads (HostEnvPool / process backend) "
                "cannot do"
            )
        if self._plane == "mesh":
            from repro.launch.mesh import make_rollout_mesh

            self._rollout_mesh = make_rollout_mesh(pipeline.mesh_shape)
            self._mesh_devices = list(self._rollout_mesh.devices.flat)
        else:
            self._rollout_mesh = None
            self._mesh_devices = None
        # shared with ParallelRL — identical RNG layout so a lock-stepped
        # single-actor pipeline reproduces the synchronous run bit-for-bit.
        (self.optimizer, self.lr_schedule, self.key, k_env, self.params,
         self.opt_state) = init_rl_common(env, agent, optimizer, lr_schedule,
                                          seed)
        if self._dqn:
            # learner-private DQN state rides the update signature next to
            # params/opt state. The target tree must be a *copy*: the first
            # update donates self.params, and an aliased target would have
            # its buffers deleted out from under the TD evaluation.
            self._target = jax.tree_util.tree_map(
                lambda a: a.copy(), self.params)
            self._updates = jnp.zeros((), jnp.int32)
        if self._plane == "mesh":
            # learner state lives replicated on the rollout mesh: every
            # device holds a full copy, the sharded step's gradient
            # all-reduce keeps the copies bit-identical, and actor lanes
            # read their device-local shard view for free
            from repro.distributed.sharding import replicated_sharding

            repl = replicated_sharding(self._rollout_mesh)
            self.params = jax.device_put(self.params, repl)
            self.opt_state = jax.device_put(self.opt_state, repl)

        act = agent.act_fn()
        if self._backend == "process":
            # no parent-side acting or env state: each worker owns its pool,
            # jitted act_step and RNG key. Key layout matches the thread
            # plane's run(); the single-worker key syncs back after each run.
            from repro.pipeline.worker import ProcessActorPlane

            self._actor_envs = self._actor_obs = self._actor_env_state = None
            self._act = self._collect_jit = None
            self._process_plane = ProcessActorPlane(
                self._proc_specs, agent, pipeline.queue_depth, self.params,
                self._actor_keys(n_actors),
            )
        else:
            self._actor_envs, self._actor_obs, self._actor_env_state = \
                self._split_envs(env, per_actor_envs, n_actors, k_env)
            if self._plane == "mesh":
                # pin each lane's carried state to its mesh device: with all
                # of a lane's inputs committed there, the shared collect jit
                # dispatches to that device (one executable per device, all
                # lanes same shapes) and its outputs land in the lane's
                # sub-ring already device-resident
                for i, d in enumerate(self._mesh_devices):
                    self._actor_obs[i] = jax.device_put(self._actor_obs[i], d)
                    self._actor_env_state[i] = jax.device_put(
                        self._actor_env_state[i], d)
            if self._host:
                from repro.pipeline.actor import make_host_act_step

                self._act = make_host_act_step(act)
                self._collect_jit = None
            else:
                self._act = None
                # all replicas share one jitted collector (same shard shapes)
                if self._dqn:
                    from repro.pipeline.offpolicy import make_dqn_collect_fn

                    self._collect_jit = jax.jit(make_dqn_collect_fn(
                        agent, self._actor_envs[0], agent.hp.t_max))
                else:
                    self._collect_jit = jax.jit(make_collect_fn(
                        act, self._actor_envs[0], agent.hp.t_max))
        # per-replica lifetime rollout counters: the DQN collector's ε-schedule
        # index (persists across run() calls, like the synchronous schedule)
        self._actor_seq = [0] * n_actors

        # the fused learner step: dequeue-consume + update + publish in one
        # dispatch. Donated: params and opt state (learner-private — actors
        # only lease ping-pong snapshots) and the stale publish buffer from
        # reserve(), each of which aliases a matching output (new params, new
        # opt state, published snapshot) so the update runs alloc-free in
        # steady state. The trajectory needs no donation: ring.get()
        # transferred sole ownership, so its buffers are reclaimed the moment
        # this execution retires them — donating them would only warn
        # (nothing output-shaped to alias). The bootstrap obs must NOT be
        # donated on the device plane: the actor carries the same array into
        # its next rollout.
        if self._plane == "mesh":
            # the sharded twin: same math, jitted with shardings, per-device
            # partial gradients all-reduced over the mesh's data axis
            self._update_step = make_sharded_learner_step(
                agent, self.optimizer, self.lr_schedule, self._rollout_mesh,
                rho_bar=pipeline.rho_bar, c_bar=pipeline.c_bar,
                fused_publish=True,
            )
        elif self._dqn:
            # the replay-fed TD step: target tree and updates counter are
            # learner-private donated state exactly like params/opt state
            from repro.pipeline.offpolicy import make_dqn_learner_step

            self._update_step = jax.jit(
                make_dqn_learner_step(agent, self.optimizer, self.lr_schedule,
                                      fused_publish=True),
                donate_argnums=(0, 1, 2, 3, 7),
            )
        else:
            self._update_step = jax.jit(
                make_learner_step(agent, self.optimizer, self.lr_schedule,
                                  rho_bar=pipeline.rho_bar,
                                  c_bar=pipeline.c_bar, fused_publish=True),
                donate_argnums=(0, 1, 5),
            )
        # one adapter per agent family so the run() loop stays agnostic:
        # (traj, last_obs, step, publish_dst) -> (published, metrics),
        # threading whatever learner-private state the step carries
        if self._dqn:
            def _apply(traj, last_obs, step_arr, publish_dst):
                (self.params, self.opt_state, self._target, self._updates,
                 published, metrics) = self._update_step(
                    self.params, self.opt_state, self._target, self._updates,
                    traj, last_obs, step_arr, publish_dst,
                )
                return published, metrics
        else:
            def _apply(traj, last_obs, step_arr, publish_dst):
                self.params, self.opt_state, published, metrics = \
                    self._update_step(
                        self.params, self.opt_state, traj, last_obs,
                        step_arr, publish_dst,
                    )
                return published, metrics
        self._apply_update = _apply
        self.total_steps = 0
        # one learned rollout = one actor shard's n_envs·t_max timesteps —
        # except on the mesh plane, where every update consumes one
        # sub-rollout from each of the n_actors lanes
        shard_envs = (self._proc_specs[0].n_envs if self._proc_specs
                      else self._actor_envs[0].n_envs)
        lanes_per_update = n_actors if self._plane == "mesh" else 1
        self._steps_per_iter = lanes_per_update * shard_envs * agent.hp.t_max
        # (actor_id, seq) of every payload consumed by the last run() —
        # the never-drop contract the pipeline tests pin down (mesh payloads
        # are lane-assembled: actor_id is -1, seq the common lane seq)
        self.learned_ids: List[Tuple[int, int]] = []

        # -- fault tolerance + checkpoint state --------------------------------
        if pipeline.fault_plan is not None and not isinstance(
                pipeline.fault_plan, FaultPlan):
            raise TypeError(
                "PipelineConfig.fault_plan must be a repro.pipeline.faults."
                f"FaultPlan, got {type(pipeline.fault_plan).__name__}"
            )
        # full (bitwise) resume needs the actor-side carried state; that only
        # exists parent-side on the thread backend's FIFO planes. Everywhere
        # else a checkpoint is a *warm* restart: params/opt state/counters
        # restore exactly, actors re-reset their envs (docs/fault_tolerance.md)
        self._ckpt_slots = (self._backend == "thread"
                            and self._plane in ("device", "host")
                            and not self._replay)
        self._iters_done = 0  # cumulative completed updates (checkpoint id)
        self._resume_step = None  # step_arr override set by restore()
        self._consumed_seq = [0] * n_actors  # per-slot consumed rollout count
        # slot -> (key, env_state, obs) after the newest *consumed* rollout
        self._live_slot_state: Dict[int, tuple] = {}
        self._resume_slot_state: Optional[Dict[int, tuple]] = None
        self.supervisor = None  # the last run()'s ActorSupervisor (elastic)
        self.actors: List = []  # the last run()'s actor replicas

    # -- queue plane ---------------------------------------------------------
    def _resolve_plane(self, plane: str) -> str:
        if plane not in ("auto", "device", "host", "mesh"):
            raise ValueError(
                "rollout_plane must be 'auto', 'device', 'host' or 'mesh', "
                f"got {plane!r}"
            )
        if self._want_mesh:
            if self._host:
                raise ValueError(
                    "rollout_plane='mesh' requires a JAX-native env: "
                    "HostEnvPool (and process-backend) rollouts are born in "
                    "host memory and cannot ride per-device sub-rings"
                )
            return "mesh"
        if plane == "auto":
            return "host" if self._host else "device"
        if plane == "device" and self._host:
            raise ValueError(
                "rollout_plane='device' requires a JAX-native env: "
                "HostEnvPool (and process-backend) rollouts are born in "
                "host memory and must ride the host TrajectoryQueue plane"
            )
        return plane

    def _make_queue(self, n_actors: int, telemetry=None):
        if self._replay:
            from repro.pipeline.replay_ring import ReplayRing

            return ReplayRing(
                capacity=self.pipeline.replay_capacity,
                batch_size=self.pipeline.replay_batch,
                producers=n_actors,
                prioritized=self.pipeline.prioritized,
                sample_seed=self._seed,
                telemetry=telemetry,
            )
        if self._plane == "mesh":
            return MeshTrajectoryRing(self.pipeline.queue_depth,
                                      self._rollout_mesh, telemetry=telemetry)
        if self._plane == "device":
            return DeviceTrajectoryRing(self.pipeline.queue_depth,
                                        producers=n_actors,
                                        telemetry=telemetry)
        return TrajectoryQueue(self.pipeline.queue_depth, producers=n_actors,
                               telemetry=telemetry)

    # -- env splitting -------------------------------------------------------
    def _split_envs(self, env, per_actor_envs, n_actors: int, k_env):
        """Per-actor env replicas + their initial obs/state.

        Returns ``(envs, obs_list, env_state_list)`` (state ``None`` per
        entry on the host path, which keeps env state inside the pool).
        """
        if per_actor_envs is not None:
            envs = per_actor_envs
            if any(hasattr(e, "step_host") != self._host for e in envs):
                raise ValueError("per-actor envs must be all host or all JAX")
            if any(e.n_envs != env.n_envs for e in envs):
                raise ValueError("per-actor envs must have equal n_envs")
        elif n_actors == 1:
            envs = [env]
        elif self._host:
            envs = env.shard(n_actors)
        else:
            if env.n_envs % n_actors:
                raise ValueError(
                    f"cannot split {env.n_envs} envs across {n_actors} actors"
                )
            envs = [narrow_vector_env(env, env.n_envs // n_actors)
                    for _ in range(n_actors)]
        if self._host:
            return envs, [e.reset() for e in envs], [None for _ in envs]
        if len(envs) == 1:
            states = [envs[0].reset(k_env)]
        else:
            states = [e.reset(k) for e, k in
                      zip(envs, jax.random.split(k_env, len(envs)))]
        return envs, [e.observe(s) for e, s in zip(envs, states)], states

    # -- rollout collection closure (runs on actor thread i) -----------------
    def _make_collect(self, i: int) -> Callable:
        """``collect(params, key) -> (key, traj, last_obs, release)``.

        Host path: rollouts accumulate into a per-actor ``HostStagingRing``
        set; ``release`` returns the set once the learner consumed it.
        Device path: the jitted collector's output feeds the ring directly
        (``release`` is ``None`` — the learner's donation recycles it).
        """
        if self._host:
            env, act, t_max = self._actor_envs[i], self._act, self.agent.hp.t_max
            staging = HostStagingRing(
                self.pipeline.queue_depth + 2, t_max, env.n_envs,
                env.obs_shape, env.obs_dtype,
            )

            def collect(params, key):
                s = staging.acquire()
                obs, key, traj, last_obs = collect_host(
                    act, env, params, self._actor_obs[i], key, t_max,
                    staging=s,
                )
                # the carried obs lives in set s; the next rollout copies it
                # out before anything can overwrite it (per-actor sets are
                # written serially by this thread only)
                self._actor_obs[i] = obs
                return key, traj, last_obs, (lambda: staging.release(s))

        else:
            collect_jit, t_max = self._collect_jit, self.agent.hp.t_max
            if self._plane == "host":
                # forced host plane on a JAX env (the GA3C-style baseline):
                # stage the device trajectory into reusable pinned buffers
                env = self._actor_envs[i]
                obs_dtype = np.asarray(self._actor_obs[i]).dtype
                staging = HostStagingRing(
                    self.pipeline.queue_depth + 2, t_max, env.n_envs,
                    env.obs_shape, obs_dtype,
                )

                def collect(params, key):
                    env_state, last_obs, key, traj = collect_jit(
                        params, self._actor_env_state[i], self._actor_obs[i],
                        key,
                    )
                    self._actor_env_state[i] = env_state
                    self._actor_obs[i] = last_obs
                    s = staging.acquire()
                    # D2H into the preallocated staging set (np.copyto pulls
                    # each device array to host exactly once, no fresh allocs)
                    for dst, src in zip(s.traj, traj):
                        np.copyto(dst, np.asarray(src))
                    np.copyto(s.last_obs, np.asarray(last_obs))
                    return key, s.traj, s.last_obs, \
                        (lambda: staging.release(s))

            elif self._plane == "mesh":
                dev = self._mesh_devices[i]
                warm = [False]  # first call compiles — exempt from the guard

                def collect(params, key):
                    # params arrive as the leased replicated snapshot; the
                    # lane consumes its zero-copy device-local view so the
                    # shared collect jit dispatches on this lane's device
                    with sanitize.guard(active=warm[0]):
                        pv = _device_view(params, dev)
                        env_state, last_obs, key, traj = collect_jit(
                            pv, self._actor_env_state[i], self._actor_obs[i],
                            key,
                        )
                        # block before the lease is released: the learner may
                        # donate the stale snapshot the moment readers reach
                        # zero, so the collect must have fully executed (and
                        # the view dropped) first — also what bounds
                        # in-flight work
                        jax.block_until_ready(traj.reward)
                    warm[0] = True
                    self._actor_env_state[i] = env_state
                    self._actor_obs[i] = last_obs
                    return key, traj, last_obs, None

            elif self._dqn:
                warm = [False]

                def collect(params, key):
                    # the ε-schedule index: this replica's lifetime rollout
                    # count (in lockstep it equals the learner step, matching
                    # the synchronous schedule). Its H2D copy is an intended
                    # edge, hoisted ahead of the transfer-guarded dispatch.
                    n = self._actor_seq[i]
                    n_dev = jnp.asarray(n, jnp.int32)
                    with sanitize.guard(active=warm[0]):
                        env_state, last_obs, key, traj = collect_jit(
                            params, self._actor_env_state[i],
                            self._actor_obs[i], key, n_dev,
                        )
                        jax.block_until_ready(traj.reward)
                    warm[0] = True
                    self._actor_seq[i] = n + 1
                    self._actor_env_state[i] = env_state
                    self._actor_obs[i] = last_obs
                    return key, traj, last_obs, None

            else:
                warm = [False]

                def collect(params, key):
                    with sanitize.guard(active=warm[0]):
                        env_state, last_obs, key, traj = collect_jit(
                            params, self._actor_env_state[i],
                            self._actor_obs[i], key,
                        )
                        # block so queue depth genuinely bounds in-flight
                        # rollouts
                        jax.block_until_ready(traj.reward)
                    warm[0] = True
                    self._actor_env_state[i] = env_state
                    self._actor_obs[i] = last_obs
                    return key, traj, last_obs, None

        return collect

    def _actor_keys(self, n_actors: int) -> List:
        if n_actors == 1:
            return [self.key]  # PR-1 layout: the single actor owns self.key
        keys = jax.random.split(self.key, n_actors + 1)
        self.key = keys[0]
        return list(keys[1:])

    # -- checkpoint / resume ---------------------------------------------------
    def _make_snapshot(self, i: int) -> Callable:
        """Post-rollout actor-state capture for slot ``i`` (thread backend).

        Called by the actor thread right after each successful collect;
        the learner stores the snapshot of the newest *consumed* rollout as
        the slot's resume point. Device path: the carried arrays are
        immutable jax values — keep references. Host path: the env state
        lives inside the pool (unrecoverable — warm restart) and the obs
        rides a recycled staging buffer, so it must be copied out.
        """
        if self._host:
            def snap(key, i=i):
                return (key, None, np.array(self._actor_obs[i]))
        else:
            def snap(key, i=i):
                return (key, self._actor_env_state[i], self._actor_obs[i])
        return snap

    def _checkpoint_template(self):
        """The checkpoint pytree *structure* (placeholder leaves carry the
        dtypes/shapes/residency ``restore_checkpoint`` restores into).
        Save and restore both derive it from the live model, so a resume
        must run under the same config — asserted by leaf-shape checks."""
        n = self._n_actors
        tree = {
            "params": self.params,
            "opt_state": self.opt_state,
            "key": self.key,
            "counters": {
                "total_steps": np.asarray(0, np.int64),
                "step_value": np.asarray(0, np.int64),
                "iters_done": np.asarray(0, np.int64),
                "actor_seq": np.zeros(n, np.int64),
                "consumed_seq": np.zeros(n, np.int64),
                # lifetime queue tickets (issued, consumed) at save time:
                # audit metadata for how many in-flight rollouts a kill
                # dropped (re-collected on resume, never silently skipped)
                "tickets": np.zeros(2, np.int64),
            },
        }
        if self._dqn:
            tree["dqn_target"] = self._target
            tree["dqn_updates"] = self._updates
        if self._ckpt_slots:
            tree["slots"] = {
                str(i): {
                    "key": jax.random.PRNGKey(0),
                    "env_state": self._actor_env_state[i],
                    "obs": self._actor_obs[i],
                }
                for i in range(n)
            }
        return tree

    @staticmethod
    def _ticket_counts(queue) -> Tuple[int, int]:
        issued = getattr(queue, "tickets_issued", 0)
        consumed = getattr(queue, "tickets_consumed", 0)
        if isinstance(issued, (list, tuple)):
            issued = sum(issued)
        if isinstance(consumed, (list, tuple)):
            consumed = sum(consumed)
        return int(issued), int(consumed)

    def _save_checkpoint(self, queue, step_value: int) -> str:
        """Snapshot the full pipeline state after the update that just
        committed. Runs on the learner thread between updates, so
        ``self.params``/``opt_state`` are quiescent; ``np.asarray`` inside
        the checkpointer blocks until the update producing them retired."""
        tree = self._checkpoint_template()
        issued, consumed = self._ticket_counts(queue)
        tree["counters"] = {
            "total_steps": np.asarray(self.total_steps, np.int64),
            "step_value": np.asarray(step_value, np.int64),
            "iters_done": np.asarray(self._iters_done, np.int64),
            "actor_seq": np.asarray(self._actor_seq, np.int64),
            "consumed_seq": np.asarray(self._consumed_seq, np.int64),
            "tickets": np.asarray([issued, consumed], np.int64),
        }
        if self._ckpt_slots:
            slots = {}
            for i in range(self._n_actors):
                st = self._live_slot_state.get(i)
                if st is None:  # nothing consumed from this slot yet
                    st = (jax.random.PRNGKey(0), self._actor_env_state[i],
                          self._actor_obs[i])
                slots[str(i)] = {"key": st[0], "env_state": st[1],
                                 "obs": st[2]}
            tree["slots"] = slots
        path = save_checkpoint(self.pipeline.checkpoint_dir,
                               self._iters_done, tree, prefix="pipe")
        log.info("checkpoint: saved %s (update %d, %d steps)",
                 path, self._iters_done, self.total_steps)
        return path

    def restore(self, directory: Optional[str] = None, *,
                prefix: str = "pipe") -> int:
        """Restore the newest checkpoint; returns the number of learner
        updates already done (0 = nothing to restore). The caller runs the
        *remaining* iterations: on the thread backend's FIFO planes the
        resumed run continues the interrupted one bitwise under lockstep
        (the tests pin this); elsewhere it is a warm restart."""
        directory = directory or self.pipeline.checkpoint_dir
        if not directory:
            raise ValueError("no checkpoint directory: pass one or set "
                             "PipelineConfig.checkpoint_dir")
        step = latest_step(directory, prefix=prefix)
        if step is None:
            return 0
        tree = restore_checkpoint(directory, step,
                                  self._checkpoint_template(), prefix=prefix)
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        self.key = tree["key"]
        if self._plane == "mesh":
            from repro.distributed.sharding import replicated_sharding

            repl = replicated_sharding(self._rollout_mesh)
            self.params = jax.device_put(self.params, repl)
            self.opt_state = jax.device_put(self.opt_state, repl)
        if self._dqn:
            self._target = tree["dqn_target"]
            self._updates = tree["dqn_updates"]
        c = tree["counters"]
        self.total_steps = int(c["total_steps"])
        self._iters_done = int(c["iters_done"])
        self._resume_step = int(c["step_value"])
        self._actor_seq = [int(x) for x in np.asarray(c["actor_seq"])]
        self._consumed_seq = [int(x) for x in np.asarray(c["consumed_seq"])]
        if self._ckpt_slots:
            self._resume_slot_state = {
                i: (tree["slots"][str(i)]["key"],
                    tree["slots"][str(i)]["env_state"],
                    tree["slots"][str(i)]["obs"])
                for i in range(self._n_actors)
            }
        issued, consumed = (int(x) for x in np.asarray(c["tickets"]))
        log.info(
            "checkpoint: restored update %d (%d steps) from %s; "
            "%d in-flight rollout(s) at save time will be re-collected",
            self._iters_done, self.total_steps, directory,
            max(issued - consumed, 0))
        return self._iters_done

    def run(self, iterations: int, log_every: int = 0) -> RunResult:
        """Run `iterations` learner updates (each = one shard's n_e·t_max
        timesteps), fed by ``num_actors`` concurrent actor replicas."""
        n_actors = self._n_actors
        # fresh telemetry hub per run (queues, actors and their emitters are
        # per-run objects); kept on self so harnesses can read the tracks —
        # e.g. benchmarks/fig2_time_split cross-checks RunResult's time
        # split against the trace — after run() returns
        hub = self.telemetry = Telemetry()
        learner_em = hub.emitter("learner")
        queue = self._make_queue(n_actors, telemetry=hub)
        if self._plane == "mesh":
            # every lane contributes one sub-rollout to every update: the
            # quota is `iterations` per lane, not split across lanes
            quota = [iterations] * n_actors
        else:
            quota = [iterations // n_actors
                     + (1 if i < iterations % n_actors else 0)
                     for i in range(n_actors)]
        cfg = self.pipeline
        # fault harness + recovery scaffolding. The injector exists with or
        # without elastic (deterministic fail-fast chaos tests); the ledger
        # and supervisor only when elastic arms recovery. Config already
        # rejected elastic on the mesh plane (fail-fast by design).
        injector = (FaultInjector(cfg.fault_plan)
                    if cfg.fault_plan is not None else None)
        elastic = cfg.elastic
        ledger = QuotaLedger(sum(quota)) if elastic else None
        ckpt_every = cfg.checkpoint_every
        snapshots = ckpt_every > 0 and self._ckpt_slots
        # resume: restore() stashed per-slot actor state; apply it exactly
        # once — the resumed actors re-enter the key/env/obs stream at the
        # checkpointed rollout boundary with seq numbering continuing where
        # the consumed stream left off (in-flight rollouts re-collect)
        resume = self._resume_slot_state
        self._resume_slot_state = None
        if resume:
            start_seqs = list(self._consumed_seq)
            self._live_slot_state = dict(resume)
        else:
            start_seqs = [0] * n_actors
            self._consumed_seq = [0] * n_actors
            self._live_slot_state = {}
        # the actor-plane split: everything below this differs by backend
        # (thread replicas collecting in-process vs subprocess workers with
        # parent-side drainers); everything after it is backend-agnostic —
        # both backends expose the same queue payloads and the same
        # reserve/commit param-slot protocol to the learner loop.
        if self._backend == "process":
            slot, actors = self._process_plane.begin_run(
                queue, quota, cfg.lockstep, self.params,
                telemetry=hub, ledger=ledger, injector=injector,
            )
        else:
            slot = PingPongParamSlot(self.params, version=0)
            keys = self._actor_keys(n_actors)
            if resume:
                keys = [resume[i][0] for i in range(n_actors)]
                for i in range(n_actors):
                    if not self._host:
                        self._actor_env_state[i] = resume[i][1]
                    self._actor_obs[i] = resume[i][2]
            if self._plane == "mesh":
                # each lane's RNG stream is pinned to its device so the
                # collect jit (whose other inputs live there) never pulls
                # the key across devices
                keys = [jax.device_put(k, d)
                        for k, d in zip(keys, self._mesh_devices)]
            actors = [
                ActorThread(
                    self._make_collect(i),
                    queue.lane(i) if self._plane == "mesh" else queue,
                    slot, key, quota[i],
                    lockstep=cfg.lockstep, actor_id=i,
                    telemetry=hub, start_seq=start_seqs[i],
                    ledger=ledger, injector=injector,
                    snapshot=self._make_snapshot(i) if snapshots else None,
                )
                for i, key in enumerate(keys)
            ]
        actors_by_id: Dict[int, object] = {a.actor_id: a for a in actors}
        sup = None
        if elastic:
            if self._backend == "process":
                def respawner(dead, new_id, remaining):
                    d = self._process_plane.respawn_worker(
                        dead.slot_index, new_id, remaining, cfg.lockstep,
                        queue, telemetry=hub, ledger=ledger,
                    )
                    actors_by_id[new_id] = d
                    d.start()
                    return d
            else:
                def respawner(dead, new_id, remaining):
                    # the replacement resumes the dead replica's RNG stream
                    # and carried env state (mutated only on a *successful*
                    # collect, so both sit at the last rollout boundary) but
                    # gets a fresh staging ring via _make_collect — the dead
                    # replica's in-flight set may be unrecoverable
                    a = ActorThread(
                        self._make_collect(dead.slot_index),
                        queue, slot, dead.key, remaining,
                        lockstep=cfg.lockstep, actor_id=new_id,
                        telemetry=hub, slot_index=dead.slot_index,
                        ledger=ledger, injector=injector,
                        snapshot=(self._make_snapshot(dead.slot_index)
                                  if snapshots else None),
                    )
                    actors_by_id[new_id] = a
                    a.start()
                    return a
            sup = ActorSupervisor(
                queue, ledger, respawner,
                restart_budget=cfg.restart_budget,
                backoff_s=cfg.restart_backoff_s, telemetry=hub,
            )
            for a in actors:
                sup.register(a)
        # kept on self (like .telemetry) so harnesses/tests can audit the
        # run's fault episodes and its replicas after run() returns
        self.supervisor = sup
        self.actors = actors
        # device plane: never sync the learner loop — metric scalars are
        # stashed and converted once at result(), so update i+1 dispatches
        # while update i still executes. Host plane: eager (the blocking
        # float() conversion is what certifies consume-completion before a
        # staging set is release()d back to its ring).
        acc = MetricsAccumulator(lazy=self._plane in ("device", "mesh"))
        self.learned_ids = []
        for a in actors:
            a.start()
        # observability side-cars: both optional, both read-only observers
        # of the emitters the hot paths write anyway
        hub.set_gauge("queue_depth", queue.qsize)
        if self.pipeline.metrics_jsonl:
            hub.heartbeat_start(
                self.pipeline.metrics_jsonl,
                interval=self.pipeline.heartbeat_s,
                actor_emitters=[a.span_emitter for a in actors],
            )
        if self.pipeline.stall_timeout_s > 0:
            hub.watchdog_start(self.pipeline.stall_timeout_s, [
                ("learner", learner_em, None),
                *[(f"actor{a.actor_id}", a.span_emitter, a.is_alive)
                  for a in actors],
            ])
        # same step-counter semantics as ParallelRL.run (lr_schedule parity);
        # a restore() overrides the start value so the resumed run's schedule
        # continues exactly where the interrupted one left off
        start_step = (self._resume_step if self._resume_step is not None
                      else self.total_steps)
        self._resume_step = None
        step_arr = jnp.asarray(start_step, jnp.int32)
        step0 = int(start_step)
        completed = 0
        # transfer sanitizer: the device planes' steady state (get → reserve
        # → fused update → commit) must stay free of implicit host traffic.
        # Iteration 0 is exempt (compilation may materialize constants); the
        # step counter bump and metric bookkeeping stay OUTSIDE the guard —
        # they are host-side by design. Host plane: the staged payload's H2D
        # is the plane's whole point, so it is never guarded.
        san = (sanitize.transfers_enabled()
               and self._plane in ("device", "mesh"))
        try:
            for i in range(iterations):
                if injector is not None:
                    injector.stall_learner(i)
                with sanitize.guard(active=san and i > 0):
                    learner_em.begin(QUEUE_GET_WAIT)
                    try:
                        payload = queue.get()
                    finally:
                        learner_em.end()
                    if payload is CLOSED:  # an actor died early
                        break
                    assert isinstance(payload, Rollout)
                    # claim the stale ping-pong buffer; bounded by one
                    # in-flight collect (actors release before blocking on the
                    # queue), so a long wait means an actor died without
                    # releasing — bail out (naming the holder) instead of
                    # hanging
                    learner_em.begin(LEASE)
                    try:
                        deadline = time.monotonic() + cfg.lease_timeout_s
                        while True:
                            publish_dst = slot.reserve(i + 1, timeout=1.0)
                            if publish_dst is not None:
                                break
                            live = (sup.all_actors() if sup is not None
                                    else actors)
                            if not any(a.is_alive() for a in live):
                                raise RuntimeError(
                                    "param lease never released "
                                    "(all actors exited)"
                                )
                            if time.monotonic() >= deadline:
                                stale = (i + 1) % 2
                                held = ", ".join(
                                    slot.holders(stale)
                                    if hasattr(slot, "holders") else ()
                                ) or "an unknown party"
                                raise RuntimeError(
                                    f"param buffer {stale} still leased after "
                                    f"lease_timeout_s={cfg.lease_timeout_s:g}s "
                                    f"— held by {held}"
                                )
                    finally:
                        learner_em.end()
                    if san:
                        prev_params = self.params
                    # on the device planes this span covers the async
                    # *dispatch*, not the execution — by design: the learner
                    # thread's own time is what the trace's learner track
                    # attributes
                    learner_em.begin(LEARNER_UPDATE)
                    try:
                        published, metrics = self._apply_update(
                            payload.traj, payload.last_obs, step_arr,
                            publish_dst,
                        )
                    finally:
                        learner_em.end()
                    learner_em.begin(PUBLISH)
                    try:
                        slot.commit(published, i + 1)
                    finally:
                        learner_em.end()
                if san:
                    # deleted-buffer probes: donation marks inputs deleted at
                    # dispatch, so still-live donated params mean aliasing
                    # was dropped and the alloc-free steady state is gone.
                    # The publish target is consistency-checked only — a
                    # backend may route the published output through the
                    # params donation and decline this alias wholesale, but
                    # a *partial* donation is always a bug.
                    sanitize.assert_deleted(prev_params, "donated params")
                    sanitize.assert_uniformly_deleted(
                        publish_dst, "reserved publish buffer")
                step_arr = step_arr + 1
                self.total_steps += self._steps_per_iter
                completed += 1
                hub.counter_add("steps", self._steps_per_iter)
                self.learned_ids.append((payload.actor_id, payload.seq))
                metrics = dict(metrics)
                metrics["staleness"] = float(i - payload.behavior_version)
                hub.set_gauge("staleness", metrics["staleness"])
                if self._replay and self.pipeline.prioritized:
                    # feed the update's |TD| back as the sampled slots' new
                    # priorities (the float() syncs on the metric scalar —
                    # the prioritized path trades one async dispatch for the
                    # feedback loop)
                    p = metrics.get("td_abs")
                    pr = float(jnp.abs(metrics["loss"]) if p is None else p)
                    queue.update_priorities(
                        queue.last_sampled,
                        [pr] * len(queue.last_sampled),
                    )
                # eager (host plane): blocks on the metric scalars => the
                # update (and the H2D copy of the staged payload) has fully
                # executed. Lazy (device plane): no sync — just stashes.
                acc.update(metrics)
                if payload.release is not None:
                    if injector is not None and injector.drop_release(i):
                        # injected lease-drop: the set is deliberately leaked
                        # — the staging ring's +2 sizing must absorb it and
                        # the run must complete regardless
                        pass
                    else:
                        payload.release()  # consume certified: set reusable
                self._iters_done += 1
                if ckpt_every:
                    # track the newest consumed rollout per slot: its
                    # post-collect actor snapshot is the slot's resume point
                    owner = actors_by_id.get(payload.actor_id)
                    if owner is not None:
                        self._consumed_seq[owner.slot_index] = payload.seq + 1
                        st = (owner.consume_state(payload.seq)
                              if hasattr(owner, "consume_state") else None)
                        if st is not None:
                            self._live_slot_state[owner.slot_index] = st
                    if completed % ckpt_every == 0:
                        self._save_checkpoint(queue, step0 + completed)
                if log_every and (i + 1) % log_every == 0:
                    # never sync the device planes for a log line: fold only
                    # the already-executed updates (cumulative() would drain
                    # every pending device scalar — a hidden blocking sync
                    # serializing the learner against its own dispatches)
                    log.info(
                        "iter %d steps %d actor %d staleness %.0f "
                        "reward_sum %.3f loss %.4f",
                        i + 1, self.total_steps, payload.actor_id,
                        metrics["staleness"],
                        acc.cumulative_nowait("reward_sum"),
                        acc.last("loss"),
                    )
        finally:
            # disarm recovery FIRST: a replica dying during teardown must
            # not respawn a fresh one under the sweeps below
            if sup is not None:
                sup.shutdown()
                actors = sup.all_actors()  # epochs included in the sweeps
            # reap all actors on every exit path (normal, learner exception,
            # KeyboardInterrupt): signal stop, then keep draining so puts
            # blocked on a full queue can finish and the threads can exit —
            # releasing discarded staged payloads so no actor can wedge on an
            # empty staging ring while unwinding.
            for a in actors:
                a.stop()
            while any(a.is_alive() for a in actors):
                try:
                    p = queue.get(timeout=0.05)
                    if p is not CLOSED and getattr(p, "release", None):
                        p.release()
                except _stdlib_queue.Empty:
                    pass
                for a in actors:
                    a.join(timeout=0.02)
            # actors are gone, but the queue may still hold unconsumed
            # payloads (learner bailed with rollouts buffered): fire their
            # release() hooks so staging buffers return to their pools —
            # on the process plane the free-lists persist across run()
            # calls, and leaked indices would starve the next run.
            while True:
                try:
                    p = queue.get(timeout=0)
                except _stdlib_queue.Empty:
                    break
                if p is CLOSED:
                    break
                if getattr(p, "release", None):
                    p.release()
            # lock-order verdict for this run: everything the sanitized
            # wrappers witnessed, attached to the trace by name so the
            # launcher (and CI) can fail on cycles/hazards post-run
            if locks_enabled():
                hub.report("lockcheck", monitor().report())
            # observers down, then export — after the joins above, so
            # worker-shipped span rings have merged into the hub. Runs on
            # every exit path: a post-mortem trace of a failed run is the
            # tool's whole point.
            hub.stop()
            if self.pipeline.trace_path:
                hub.write_trace(self.pipeline.trace_path)
        if sup is not None and sup.fatal is not None:
            raise RuntimeError(
                f"pipeline stopped early after faults: {completed}/"
                f"{iterations} iterations — last live actor died"
            ) from sup.fatal.error
        # supervised deaths (fault_handled) were absorbed — respawned or
        # degraded — and must not fail a run that completed its quota
        errors = [a for a in actors
                  if a.error is not None
                  and not getattr(a, "fault_handled", False)]
        if errors:
            raise RuntimeError(
                f"pipeline actor {errors[0].actor_id} failed"
            ) from errors[0].error
        if completed != iterations:
            raise RuntimeError(
                f"pipeline stopped early: {completed}/{iterations} iterations"
            )
        if sup is not None and sup.episodes:
            log.warning("pipeline recovered from %d fault episode(s): %s",
                        len(sup.episodes), sup.episodes)
        if n_actors == 1:
            # with a supervisor the slot's newest epoch carries the stream
            last = sup.slot_actor(0) if sup is not None else actors[0]
            if self._backend == "process":
                # the worker owns the acting key; sync it back so repeated
                # run() calls continue the same stream the thread plane would
                if last.final_key is not None:
                    self.key = jnp.asarray(last.final_key)
            else:
                self.key = last.key
        per_actor_idle = [a.put_wait_s + a.wait_s for a in actors]
        # the end-of-run metrics drain pulls every stashed device scalar to
        # host in one batch — the device planes' one intended D2H sync
        with sanitize.allowed("metrics drain"):
            return acc.result(
                self.total_steps,
                self._steps_per_iter,
                actor_idle_s=sum(per_actor_idle),
                learner_idle_s=queue.get_wait_s,
                per_actor_idle_s=per_actor_idle,
            )

    # -- teardown (process plane + pools built from specs) -------------------
    def close(self) -> None:
        """Release resources this backend *owns*: worker subprocesses and
        their shared memory (process backend), and any ``HostEnvPool`` built
        here from a ``HostEnvSpec``. Live pools the caller handed in stay
        the caller's to close. Idempotent."""
        if self._process_plane is not None:
            self._process_plane.close()
            self._process_plane = None
        for pool in self._owned_pools:
            pool.close()
        self._owned_pools = []

    def __enter__(self) -> "PipelinedRL":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
