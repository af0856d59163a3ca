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

``ParallelRL.run`` records its host loop into a ``repro.telemetry``
emitter (track ``driver`` of a per-run hub, kept as ``self.telemetry``):
``step.dispatch`` around the train step's call, ``metrics.read`` around
the host's read of the update's metrics. ``RunResult.dispatch_s`` /
``readback_s`` are those spans' totals. While the JAX profiler collects,
the spans and a ``StepTraceAnnotation`` per iteration land in the profile.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

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


class MetricsAccumulator:
    """Shared run-loop accounting: per-iteration metric dicts → RunResult.

    Used by both the synchronous ``ParallelRL`` loop and the pipelined
    learner loop so the two backends report identical metric semantics
    (mean-per-iteration metrics, episode counts, timesteps/s over the run's
    wall-clock).

    ``lazy=True`` defers the host conversion of device metric scalars: each
    ``update`` only stashes the dict, and the blocking ``float()`` reads
    happen once, in ``result``. Eager mode forces a device sync every
    iteration — which the synchronous loop doesn't notice (it waits for the
    update anyway) and the host queue plane *requires* (consume-completion
    gates the staging ``release`` protocol), but which would serialize the
    device-ring learner against every update it dispatches. Lazy draining
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
        self._last: Dict = {}  # most recently *folded* metrics dict
        self._t0 = time.perf_counter()

    def update(self, metrics: Dict) -> None:
        self.iters += 1
        if self.lazy:
            self._pending.append(metrics)
            return
        self._fold(metrics)

    def _fold(self, metrics: Dict) -> None:
        # each value is read once: float() of a device array is one
        # device-to-host transfer (one ``np.asarray(jax.Array)`` event in a
        # profile)
        host = {}
        for k, v in metrics.items():
            self.host_reads += isinstance(v, jax.Array)
            host[k] = float(v)
            self.acc[k] = self.acc.get(k, 0.0) + host[k]
        self.episodes += host.get("episodes", 0.0)
        self._last = metrics

    def _drain(self) -> None:
        for metrics in self._pending:
            self._fold(metrics)
        self._pending.clear()

    @staticmethod
    def _ready(metrics: Dict) -> bool:
        # jax.Array.is_ready() == "execution producing this buffer retired";
        # host values (python/numpy scalars) have no is_ready and are ready
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
            # consumes each update (MetricsAccumulator blocks on the metric
            # scalars) before the next rollout overwrites the buffers, so a
            # single set is race-free — zero numpy allocation per iteration
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
        self.total_steps = 0
        self._steps_per_iter = env.n_envs * agent.hp.t_max

    # -- the HostEnvPool path's host rollout ---------------------------------
    def _host_collect(self):
        self.obs, self.key, traj, last_obs = self._collect_host(
            self._act, self.env, self.params, self.obs, self.key,
            self.agent.hp.t_max, staging=self._staging,
        )
        return traj, last_obs

    def compiled(self):
        """The train step as XLA compiled it for this job
        (``jax.stages.Compiled``; its ``as_text()`` names every instruction
        with the ``op_name`` scope path it came from). A repeat of the
        program ``run`` compiled is answered by the compile cache."""
        if self._host:
            raise NotImplementedError(
                "the HostEnvPool path has no fused train step")
        state = (self.params, self.opt_state) + (
            (self.agent_state,) if self._has_agent_state else ()
        ) + (self.env_state, self.obs, self.key)
        step = jnp.asarray(0, jnp.int32)
        return self._train_step.lower(*state, step).compile()

    def _dispatch(self, step_arr, batch=None):
        """One call of the jitted step: the fused train step, or on the
        HostEnvPool path the update on the collected ``batch``."""
        if self._host:
            traj, last_obs = batch
            self.params, self.opt_state, metrics = self._update_step(
                self.params, self.opt_state, traj, last_obs, step_arr
            )
        elif self._has_agent_state:
            (
                self.params,
                self.opt_state,
                self.agent_state,
                self.env_state,
                self.obs,
                self.key,
                metrics,
            ) = self._train_step(
                self.params, self.opt_state, self.agent_state,
                self.env_state, self.obs, self.key, step_arr,
            )
        else:
            (
                self.params,
                self.opt_state,
                self.env_state,
                self.obs,
                self.key,
                metrics,
            ) = self._train_step(
                self.params, self.opt_state, self.env_state, self.obs,
                self.key, step_arr,
            )
        return metrics

    def run(self, iterations: int, log_every: int = 0) -> RunResult:
        """Run `iterations` framework iterations (each = n_e·t_max timesteps)."""
        # fresh telemetry hub per run, kept on self like PipelinedRL's
        self.telemetry = Telemetry()
        spans = self.telemetry.emitter("driver")
        acc = MetricsAccumulator()
        step_arr = jnp.asarray(self.total_steps, jnp.int32)
        batch = None
        for i in range(iterations):
            with step_annotation("train",
                                 self.total_steps // self._steps_per_iter):
                if self._host:
                    batch = self._host_collect()
                spans.begin(STEP_DISPATCH)
                metrics = self._dispatch(step_arr, batch)
                step_arr = step_arr + 1
                spans.end()
                self.total_steps += self._steps_per_iter
                spans.begin(METRICS_READ)
                acc.update(metrics)
                spans.end()
            if log_every and (i + 1) % log_every == 0:
                log.info(
                    "iter %d steps %d reward_sum %.3f loss %.4f",
                    i + 1, self.total_steps,
                    acc.acc.get("reward_sum", 0.0),
                    float(metrics.get("loss", 0.0)),
                )
        return acc.result(self.total_steps, self._steps_per_iter,
                          dispatch_s=spans.total(STEP_DISPATCH),
                          readback_s=spans.total(METRICS_READ))
