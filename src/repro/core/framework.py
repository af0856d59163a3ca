"""PAAC framework orchestrator — paper Algorithm 1 end to end.

``ParallelRL`` wires environments + agent + optimizer into a single jitted
``train_step`` and runs the outer ``until N >= N_max`` loop (line 3/20) on
the host, tracking throughput (timesteps/s — the paper's Fig. 2/4 metric)
and episode returns.

Two environment regimes:

* JAX-native ``VectorEnv`` — acting, stepping and learning fuse into one
  XLA program per iteration (the fast path).
* ``HostEnvPool`` — external gym-style envs stepped by host worker threads
  (paper §3 literally). Here one iteration is a host-side rollout (jitted
  acting, threaded env stepping) followed by a jitted update. This is the
  paper's Fig. 2 "env time on the critical path" regime; the asynchronous
  pipeline (``repro.pipeline``) exists to overlap exactly that stall.

The run-loop metrics accounting is shared with ``repro.pipeline`` through
``MetricsAccumulator`` so both backends report identical ``RunResult``s.

On the fused path one dispatch runs up to ``UPDATES_PER_DISPATCH``
consecutive updates in one program (a ``fori_loop`` over the train step
with the trip count as an argument, so one executable serves every
chunk), and hands back their metric scalars packed in one array. The
host pays a dispatch's cost (the call, one transfer, one fold) once per
chunk. Each chunk's metrics are read one chunk late: the host copy of
chunk j's array starts as j is dispatched, and it is folded once chunk
j+1 is queued behind it, so the device never drains while the host
reads. The ``HostEnvPool`` path runs one update per dispatch and folds
it before its next collect (its staging set is reused).

``ParallelRL.run`` records its host loop into a ``repro.telemetry``
emitter (track ``driver`` of a per-run hub, kept as ``self.telemetry``):
``step.dispatch`` around the train step's call, ``metrics.read`` around
the host's fold of a dispatch's metrics (the previous chunk's, on the
fused path). ``RunResult.dispatch_s`` / ``readback_s`` are those spans'
totals, ``dispatches`` (hub counter ``dispatches``) counts the train
step's calls, and ``reads_waited`` (hub counter ``reads_waited``) counts
the deferred folds that still waited on the device. While the JAX
profiler collects, the spans and a ``StepTraceAnnotation`` per dispatch
land in the profile.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.agents.base import Agent
from repro.core.agents.dqn import DQNAgent
from repro.core.agents.baselines import LaggedPAACAgent
from repro.envs.host_env import HostEnvPool
from repro.models import init_policy
from repro.optim import make_optimizer
from repro.telemetry import (
    METRICS_READ,
    STEP_DISPATCH,
    Telemetry,
    step_annotation,
)
from repro.utils import get_logger

log = get_logger("framework")

# hub counter of the sync driver's deferred folds that waited on the device
READS_WAITED = "reads_waited"
# hub counter of the sync driver's train-step calls
DISPATCHES = "dispatches"
# Updates one fused-path dispatch runs at most. A dispatch costs the host
# the same whatever the update's size: on a TPU v5e about 3.4-3.6 ms in
# the paper's n_e=32 jobs, against 1.0-1.1 ms of device work an update
# (PERF.md, section 5). Eight updates give the device 7.9-9.0 ms of work
# per dispatch, over twice the host's cost; four would leave almost no
# margin. Jobs whose update outlasts the dispatch lose nothing: their
# host was hidden already, and the loop costs a compare per update.
UPDATES_PER_DISPATCH = 8
# train-step metrics that count work (summed over a run, not averaged in
# meaning) and the monitoring event each run's total is published under
COUNTER_PREFIX = "count."
RUN_COUNTER_EVENT = "/repro/run/"


@dataclass
class RunResult:
    steps: int
    episodes: float
    mean_metrics: Dict[str, float]
    episode_reward_rate: List[float] = field(default_factory=list)
    timesteps_per_sec: float = 0.0
    # pipeline accounting (0 for the synchronous backend): time the actors
    # spent blocked on a full queue / waiting for params (merged across
    # replicas), and time the learner spent blocked on an empty queue.
    # ``per_actor_idle_s[i]`` attributes the merged actor idle time to
    # replica i; it sums to ``actor_idle_s`` exactly.
    actor_idle_s: float = 0.0
    learner_idle_s: float = 0.0
    per_actor_idle_s: List[float] = field(default_factory=list)
    # synchronous driver accounting (0 for the pipeline): host time in the
    # train step's dispatch and in reading the update's metrics back (span
    # totals), and the device-to-host transfers those reads made (every
    # backend: one per device metric scalar brought to the host)
    dispatch_s: float = 0.0
    readback_s: float = 0.0
    host_reads: int = 0
    # deferred folds of the fused sync path whose chunk had not retired
    # when the fold began: the host waited on the device, with the next
    # chunk already queued behind it (0 on the HostEnvPool path, which
    # defers nothing, and for the pipeline)
    reads_waited: int = 0
    # train-step calls of the synchronous driver: one per chunk of up to
    # UPDATES_PER_DISPATCH updates on the fused path, one per update on the
    # HostEnvPool path (0 for the pipeline)
    dispatches: int = 0


class PackedMetrics(NamedTuple):
    """The metric scalars of ``updates`` consecutive updates in one device
    array: row i holds update i's, in the order of ``keys``; rows from
    ``updates`` on are unused."""

    rows: jax.Array
    keys: Tuple[str, ...]
    updates: int


class MetricsAccumulator:
    """Shared run-loop accounting: per-iteration metric dicts → RunResult.

    Used by both the synchronous ``ParallelRL`` loop and the pipelined
    learner loop so the two backends report identical metric semantics
    (mean-per-iteration metrics, episode counts, timesteps/s over the run's
    wall-clock). ``update`` takes one update's dict of scalars, or a
    ``PackedMetrics`` of several updates (the fused sync path): that is
    brought to the host in one transfer and folded row by row, in the same
    float arithmetic and order as the rows' dicts would be.

    ``lazy=True`` defers the host conversion of device metric scalars: each
    ``update`` only stashes the dict, and the blocking ``float()`` reads
    happen once, in ``result``. Eager mode waits for the update it is
    given. The host queue plane and the synchronous ``HostEnvPool`` path
    *require* that (consume-completion gates the reuse of their staging
    buffers); the fused synchronous loop hands each update over one update
    late, so the wait overlaps the next one; the device-ring learner would
    be serialized against every update it dispatches. Lazy draining
    accumulates in exactly the same host-side float arithmetic, so the two
    modes report bit-identical metrics; the wall clock is read *after* the
    drain, so timesteps/s still covers the full execution, not just the
    dispatches.
    """

    def __init__(self, lazy: bool = False):
        self.acc: Dict[str, float] = {}
        self.episodes = 0.0
        self.iters = 0
        self.lazy = lazy
        self.host_reads = 0  # device arrays brought to the host by _fold
        self._pending: List[Dict] = []
        self._last: Dict = {}  # most recently *folded* metrics, host-side
        self._t0 = time.perf_counter()

    def update(self, metrics) -> None:
        self.iters += (metrics.updates if isinstance(metrics, PackedMetrics)
                       else 1)
        if self.lazy:
            self._pending.append(metrics)
            return
        self._fold(metrics)

    def _fold(self, metrics) -> None:
        # each device array is read once: one device-to-host transfer (one
        # ``np.asarray(jax.Array)`` event in a profile; a bare np.asarray
        # takes a path that records none)
        if isinstance(metrics, PackedMetrics):
            self.host_reads += 1
            rows = jax.device_get(metrics.rows)
            for row in rows[:metrics.updates]:
                self._add(dict(zip(metrics.keys, map(float, row))))
            return
        host = {}
        for k, v in metrics.items():
            self.host_reads += isinstance(v, jax.Array)
            host[k] = float(v)
        self._add(host)

    def _add(self, host: Dict[str, float]) -> None:
        for k, v in host.items():
            self.acc[k] = self.acc.get(k, 0.0) + v
        self.episodes += host.get("episodes", 0.0)
        self._last = host

    def _drain(self) -> None:
        for metrics in self._pending:
            self._fold(metrics)
        self._pending.clear()

    @staticmethod
    def _ready(metrics) -> bool:
        # jax.Array.is_ready() == "execution producing this buffer retired";
        # host values (python/numpy scalars) have no is_ready and are ready
        if isinstance(metrics, PackedMetrics):
            return metrics.rows.is_ready()
        return all(
            is_ready() if (is_ready := getattr(v, "is_ready", None)) else True
            for v in metrics.values()
        )

    def drain_ready(self) -> None:
        """Fold only the pending dicts whose device scalars have already
        materialized, front of the queue first, stopping at the first
        still-executing update. Never blocks and never forces a device
        sync — the in-flight tail keeps pipelining."""
        while self._pending and self._ready(self._pending[0]):
            self._fold(self._pending.pop(0))

    def cumulative(self, key: str, default: float = 0.0) -> float:
        """Running sum of one metric (drains pending device scalars first —
        a sync point, so only for explicit logging paths)."""
        self._drain()
        return self.acc.get(key, default)

    def cumulative_nowait(self, key: str, default: float = 0.0) -> float:
        """Running sum over *already-executed* updates only: the hot-loop
        logging read. Same float arithmetic as ``cumulative`` but the tail
        of still-dispatching updates is simply not yet included."""
        self.drain_ready()
        return self.acc.get(key, default)

    def last(self, key: str, default: float = 0.0) -> float:
        """Latest folded value of one metric (already host-side — free)."""
        return float(self._last.get(key, default))

    def result(self, steps: int, steps_per_iter: int, **extra) -> RunResult:
        self._drain()  # blocks until every dispatched update has executed
        dt = time.perf_counter() - self._t0
        mean = {k: v / max(self.iters, 1) for k, v in self.acc.items()}
        return RunResult(
            steps=steps,
            episodes=self.episodes,
            mean_metrics=mean,
            timesteps_per_sec=steps_per_iter * self.iters / max(dt, 1e-9),
            host_reads=self.host_reads,
            **extra,
        )


def loop_train_step(train_step, keys: Tuple[str, ...]):
    """``train_step`` run ``trips`` times in one program.

    ``looped(state, step0, trips) -> (state, step0 + trips, rows)``: a
    ``fori_loop`` with a dynamic bound carries the train step's state
    (its arguments but the step counter), giving update i the counter
    ``step0 + i``. ``rows`` is f32[UPDATES_PER_DISPATCH, len(keys)]: row i
    holds update i's metrics in the order of ``keys``, rows from ``trips``
    on are zero. ``trips`` is an int32 scalar at most UPDATES_PER_DISPATCH.
    Metric scalars must be exact in f32 (f32, bf16, bool, small ints)."""

    def looped(state, step0, trips):
        def body(i, carry):
            state, rows = carry
            *state, metrics = train_step(*state, step0 + i)
            row = jnp.stack([metrics[k].astype(jnp.float32) for k in keys])
            return tuple(state), rows.at[i].set(row)

        rows = jnp.zeros((UPDATES_PER_DISPATCH, len(keys)), jnp.float32)
        state, rows = jax.lax.fori_loop(0, trips, body, (tuple(state), rows))
        return state, step0 + trips, rows

    return looped


def init_rl_common(env, agent, optimizer: str, lr_schedule, seed: int):
    """Shared constructor half of ``ParallelRL`` and ``PipelinedRL``.

    Returns ``(optimizer, lr_schedule, key, k_env, params, opt_state)``. The
    RNG layout here is load-bearing: both backends must split the seed key
    identically so a lock-stepped pipeline reproduces the synchronous run
    bit-for-bit.
    """
    opt = make_optimizer(optimizer)
    if lr_schedule is None:
        from repro.optim import constant

        lr_schedule = constant(0.0007 * env.n_envs)  # paper §5.2 rule
    key, k_init, k_env = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = init_policy(k_init, agent.cfg)
    return opt, lr_schedule, key, k_env, params, opt.init(params)


class ParallelRL:
    """The paper's master/worker framework, compiled to one program/iteration."""

    def __init__(
        self,
        env,
        agent: Agent,
        *,
        optimizer: str = "rmsprop",
        lr_schedule: Optional[Callable] = None,
        seed: int = 0,
        replay_capacity: int = 50_000,
    ):
        self.env = env
        self.agent = agent
        (self.optimizer, self.lr_schedule, self.key, k_env, self.params,
         self.opt_state) = init_rl_common(env, agent, optimizer, lr_schedule,
                                          seed)

        self._host = isinstance(env, HostEnvPool)
        if self._host:
            from repro.core.agents.paac import PAACAgent

            # exact type: subclasses/look-alikes (LaggedPAACAgent, PPOAgent,
            # DQNAgent) need their own update step, which the shared host
            # learner step would silently replace with the plain PAAC loss
            if type(agent) is not PAACAgent:
                raise NotImplementedError(
                    "HostEnvPool currently drives plain PAACAgent "
                    f"(got {type(agent).__name__})"
                )
            self._has_agent_state = False
            self.agent_state = None
            self.env_state = None
            self.obs = env.reset()
            from repro.pipeline.actor import (
                StagingSet,
                collect_host,
                make_host_act_step,
            )

            self._collect_host = collect_host
            self._act = make_host_act_step(agent.act_fn())
            # one reusable trajectory staging set: the synchronous loop fully
            # consumes each update (``run`` folds this path's metric scalars
            # at once, not one update late) before the next rollout
            # overwrites the buffers, so a single set is race-free — zero
            # numpy allocation per iteration
            self._staging = StagingSet(agent.hp.t_max, env.n_envs,
                                       env.obs_shape, env.obs_dtype)
            # shared with the pipelined learner: same jitted update step,
            # with infinite V-trace clips — the correction compiled out
            # exactly (behaviour == learner here), so a lock-stepped pipeline
            # matches this driver bit-for-bit.
            from repro.pipeline.learner import make_learner_step

            self._update_step = jax.jit(
                make_learner_step(agent, self.optimizer, self.lr_schedule,
                                  rho_bar=float("inf"), c_bar=float("inf")),
                donate_argnums=(1,),
            )
            self._train_step = None
        else:
            self.env_state = env.reset(k_env)
            self.obs = env.observe(self.env_state)

            self._has_agent_state = isinstance(agent, (DQNAgent, LaggedPAACAgent))
            if isinstance(agent, DQNAgent):
                self.agent_state = agent.init_state(
                    replay_capacity, env.obs_shape, self.params, self.obs.dtype
                )
            elif isinstance(agent, LaggedPAACAgent):
                self.agent_state = agent.init_state(self.params)
            else:
                self.agent_state = None

            self._train_step = jax.jit(
                agent.make_train_step(env, self.optimizer, self.lr_schedule)
            )
        # the looped program, built from ``self._train_step`` at its first
        # use, and its metric keys
        self._loop = None
        self._metric_keys: Tuple[str, ...] = ()
        self.total_steps = 0
        self._steps_per_iter = env.n_envs * agent.hp.t_max

    # -- the HostEnvPool path's host rollout ---------------------------------
    def _host_collect(self):
        self.obs, self.key, traj, last_obs = self._collect_host(
            self._act, self.env, self.params, self.obs, self.key,
            self.agent.hp.t_max, staging=self._staging,
        )
        return traj, last_obs

    def _state(self) -> tuple:
        """The fused train step's arguments but the step counter."""
        return (self.params, self.opt_state) + (
            (self.agent_state,) if self._has_agent_state else ()
        ) + (self.env_state, self.obs, self.key)

    def _set_state(self, state: tuple) -> None:
        if self._has_agent_state:
            (self.params, self.opt_state, self.agent_state, self.env_state,
             self.obs, self.key) = state
        else:
            (self.params, self.opt_state, self.env_state, self.obs,
             self.key) = state

    def _looped(self):
        """The jitted ``loop_train_step`` of ``self._train_step`` as it is
        at the first call (a replacement made before then reaches every
        update). Tracing the step here for its metric keys costs nothing
        more: the loop's body reuses the trace."""
        if self._loop is None:
            step = self._train_step
            counter = jax.ShapeDtypeStruct((), jnp.int32)
            metrics = jax.eval_shape(step, *self._state(), counter)[-1]
            self._metric_keys = tuple(sorted(metrics))
            self._loop = jax.jit(loop_train_step(step, self._metric_keys))
        return self._loop

    def compiled(self):
        """The program a fused-path dispatch runs, as XLA compiled it for
        this job (``jax.stages.Compiled``; its ``as_text()`` names every
        instruction with the ``op_name`` scope path it came from). Lowered
        with the arguments ``run`` passes, so a repeat of the program
        ``run`` compiled is answered by the compile cache."""
        if self._host:
            raise NotImplementedError(
                "the HostEnvPool path has no fused train step")
        step, trips = jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32)
        return self._looped().lower(self._state(), step, trips).compile()

    def _dispatch(self, step_arr, updates: int, batch=None):
        """One call of a jitted step; returns the next update's step
        counter and the metrics. Fused path: ``updates`` (at most
        ``UPDATES_PER_DISPATCH``) consecutive train steps in one program,
        their metrics a ``PackedMetrics``. ``HostEnvPool`` path: the update
        on the collected ``batch``, its metrics a dict."""
        if self._host:
            traj, last_obs = batch
            self.params, self.opt_state, metrics = self._update_step(
                self.params, self.opt_state, traj, last_obs, step_arr
            )
            return step_arr + 1, metrics
        state, step_arr, rows = self._looped()(
            self._state(), step_arr, jnp.asarray(updates, jnp.int32))
        self._set_state(state)
        return step_arr, PackedMetrics(rows, self._metric_keys, updates)

    def run(self, iterations: int, log_every: int = 0) -> RunResult:
        """Run `iterations` framework iterations (each = n_e·t_max timesteps).

        On the fused path the iterations go out in chunks of up to
        ``UPDATES_PER_DISPATCH`` updates, one dispatch each, and each
        chunk's metrics are folded one chunk late: after dispatching chunk
        j the host starts the copy of j's packed metrics and folds chunk
        j−1's, so at most two chunks are in flight and the host only
        blocks on a chunk with the next one queued behind it. The last
        chunk is folded after the loop, before the clock is read. The
        ``HostEnvPool`` path dispatches one update at a time and folds it
        at once: its next collect reuses the staging buffers the update
        reads. ``log_every`` logs at the first dispatch boundary at or past
        each multiple.
        """
        # fresh telemetry hub per run, kept on self like PipelinedRL's
        self.telemetry = Telemetry()
        spans = self.telemetry.emitter("driver")
        acc = MetricsAccumulator()
        chunk = 1 if self._host else UPDATES_PER_DISPATCH

        def read(metrics, deferred: bool) -> None:
            spans.begin(METRICS_READ)
            if deferred and not MetricsAccumulator._ready(metrics):
                self.telemetry.counter_add(READS_WAITED, 1)
            acc.update(metrics)
            spans.end()

        step_arr = jnp.asarray(self.total_steps, jnp.int32)
        batch = previous = None
        done = logged = 0
        while done < iterations:
            updates = min(chunk, iterations - done)
            with step_annotation("train",
                                 self.total_steps // self._steps_per_iter):
                if self._host:
                    batch = self._host_collect()
                spans.begin(STEP_DISPATCH)
                step_arr, metrics = self._dispatch(step_arr, updates, batch)
                if not self._host:
                    metrics.rows.copy_to_host_async()
                spans.end()
                self.telemetry.counter_add(DISPATCHES, 1)
                done += updates
                self.total_steps += updates * self._steps_per_iter
                if self._host:
                    read(metrics, deferred=False)
                else:
                    if previous is not None:
                        read(previous, deferred=True)
                    previous = metrics
            if log_every and done // log_every > logged:
                logged = done // log_every
                # the folded updates only: no read of the chunk in flight
                log.info(
                    "iter %d steps %d reward_sum %.3f loss %.4f",
                    done, self.total_steps,
                    acc.acc.get("reward_sum", 0.0), acc.last("loss"),
                )
        if previous is not None:
            read(previous, deferred=True)
        result = acc.result(
            self.total_steps, self._steps_per_iter,
            dispatch_s=spans.total(STEP_DISPATCH),
            readback_s=spans.total(METRICS_READ),
            reads_waited=int(self.telemetry.counter(READS_WAITED)),
            dispatches=int(self.telemetry.counter(DISPATCHES)))
        publish_counters(acc.acc)
        return result


def publish_counters(totals: Dict[str, float]) -> None:
    """Hand each counter's run total (metrics named ``count.*``) to JAX's
    monitoring listeners as the scalar ``/repro/run/<name>``, so that an
    observer of the process reads them without the ``RunResult``."""
    for name, total in totals.items():
        if name.startswith(COUNTER_PREFIX):
            jax.monitoring.record_scalar(RUN_COUNTER_EVENT + name, total)
