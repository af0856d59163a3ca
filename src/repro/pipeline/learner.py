"""The pipeline's learning half: V-trace-corrected PAAC update.

The learner consumes rollouts that may be several updates stale (up to
``queue_depth``, from any of the ``num_actors`` replicas). Following IMPALA
(Espeholt et al., 2018), the n-step targets are replaced by full V-trace:

    ρ_t = min(ρ̄, π_learner(a_t|s_t) / π_behaviour(a_t|s_t))
    c_t = min(c̄, π_learner(a_t|s_t) / π_behaviour(a_t|s_t))
    δ_t = ρ_t (r_t + γ_t V(s_{t+1}) − V(s_t))
    v_t = V(s_t) + δ_t + γ_t c_t (v_{t+1} − V(s_{t+1}))

with the behaviour log-prob recorded at acting time (``Transition.logp``),
values recomputed under current params, and the policy gradient driven by
ρ_t (r_t + γ_t v_{t+1} − V(s_t)). The ρ̄ clip bounds each step's correction
(PR-1's per-step clip); the c̄ *product* additionally discounts how far a
correction propagates backwards, which is what keeps queues deeper than 2
unbiased.

ρ̄ = c̄ = ∞ (literally ``float("inf")``) is the synchronous limit: the
correction is compiled out and the step computes the plain PAAC loss on
n-step returns — bit-for-bit the synchronous update, which is how the
lockstep equivalence tests pin the pipeline to ``ParallelRL``.

``make_learner_step`` returns a jittable
``(params, opt_state, traj, last_obs, step) -> (params, opt_state, metrics)``
— the learning half of ``PAACAgent.make_train_step`` with the rollout
replaced by a queue payload. The synchronous ``HostEnvPool`` driver in
``repro.core.framework`` reuses the same step (with infinite clips), so sync
and pipelined backends differ only in overlap, not in math.

With ``fused_publish=True`` the step also produces the actor-facing param
snapshot inside the same program —
``(params, opt_state, traj, last_obs, step, publish_dst) ->
(params, opt_state, published, metrics)`` — so one dispatch per iteration
covers dequeue-consume, update, *and* publish. ``published`` is a bitwise
copy of the new params (the publish copy cannot perturb the lockstep
guarantee), and ``publish_dst`` is the stale ping-pong buffer from
``PingPongParamSlot.reserve``: donated, so backends that realize
input/output aliasing write the snapshot straight over it. This is the
shape that makes full donation safe — the orchestrator jits it with
``donate_argnums`` on params, opt state, and the publish target (each
aliases a shape-identical output, so the update is allocation-free in
steady state), and actors never see a donated buffer because they only
ever lease the published copies.

``make_sharded_learner_step`` is the mesh twin: the same update jitted with
``NamedSharding``s over a 1-axis ``("data",)`` rollout mesh — trajectory and
bootstrap batch sharded along the env axis, params/opt state replicated —
so XLA's SPMD partitioner turns the batch-mean gradients into per-device
partial gradients plus an all-reduce across the data axis (Stooke & Abbeel
2018's synchronous multi-GPU step). The fused-publish donation path is
preserved verbatim: params, opt state and the stale publish buffer are
donated replicated trees whose shards alias the outputs shard-for-shard, so
the sharded update is just as allocation-free as the single-device one. On
a 1-device mesh the partitioner's annotations are no-ops and the step is
bit-identical to the flat ``make_learner_step`` jit (pinned by the mesh=1
lockstep test).
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.agents.paac import (
    learn,
    paac_losses,
    trajectory_forward,
    trajectory_logits_values,
)
from repro.core.returns import vtrace_returns


def make_learner_step(agent, optimizer, lr_schedule, rho_bar: float = 1.0,
                      c_bar: float = 1.0,
                      fused_publish: bool = False) -> Callable:
    """Build the pipelined learner's jittable update step for a PAAC agent.

    ``fused_publish=False`` (default): the PR-1/PR-2 signature, shared with
    the synchronous ``HostEnvPool`` driver. ``fused_publish=True``: the
    donation-ready signature described in the module docstring (extra
    ``publish_dst`` argument, extra ``published`` output).
    """
    cfg, hp = agent.cfg, agent.hp
    act = agent.act_fn()
    # the clips are static: the infinite-clip (synchronous) limit is resolved
    # at trace time so it shares the sync path's computation graph exactly
    exact_sync = math.isinf(rho_bar) and math.isinf(c_bar)

    def _rho(logits, actions, behaviour_logp):
        logp_now = jnp.take_along_axis(
            jax.nn.log_softmax(logits), actions[:, None], axis=1
        )[:, 0]
        rho = jnp.exp(
            logp_now - behaviour_logp.reshape(logp_now.shape).astype(jnp.float32)
        )
        return logp_now, jax.lax.stop_gradient(rho)

    def loss_sync(params, traj, bootstrap):
        # ρ̄ = c̄ = ∞: correction disabled — the paper's on-policy loss,
        # identical graph to the synchronous train step (bitwise lockstep)
        logits, values, actions, returns = trajectory_forward(
            params, cfg, hp, traj, bootstrap
        )
        _, rho = _rho(logits, actions, traj.logp)
        total, metrics = paac_losses(
            logits, values, actions, returns, hp.entropy_beta, hp.value_coef
        )
        return total, metrics, rho

    def loss_vtrace(params, traj, bootstrap):
        T, E = traj.action.shape
        logits, values = trajectory_logits_values(params, cfg, traj)
        actions = traj.action.reshape(T * E)
        logp_now, rho = _rho(logits, actions, traj.logp)
        # V-trace runs on (E, T) matrices; the flattened batch is time-major
        vs, pg_adv = vtrace_returns(
            traj.reward.T,
            traj.done.T,
            jax.lax.stop_gradient(values).reshape(T, E).T,
            jax.lax.stop_gradient(bootstrap),
            rho.reshape(T, E).T,
            hp.gamma,
            rho_bar,
            c_bar,
        )
        vs = jax.lax.stop_gradient(vs.T.reshape(T * E))
        pg_adv = jax.lax.stop_gradient(pg_adv.T.reshape(T * E))
        logp_all = jax.nn.log_softmax(logits)
        policy_loss = -jnp.mean(pg_adv * logp_now)
        entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
        value_loss = jnp.mean(jnp.square(vs - values))
        total = policy_loss - hp.entropy_beta * entropy \
            + hp.value_coef * value_loss
        return total, {
            "policy_loss": policy_loss,
            "value_loss": value_loss,
            "entropy": entropy,
        }, rho

    def loss_fn(params, traj, bootstrap):
        fn = loss_sync if exact_sync else loss_vtrace
        total, metrics, rho = fn(params, traj, bootstrap)
        metrics["rho_mean"] = jnp.mean(rho)
        metrics["rho_clip_frac"] = jnp.mean((rho > rho_bar).astype(jnp.float32))
        metrics["c_clip_frac"] = jnp.mean((rho > c_bar).astype(jnp.float32))
        return total, metrics

    def _update(params, opt_state, traj, last_obs, step):
        # V(s_{tmax+1}) under learner params, then the same learning half
        # (and name scopes) as the fused synchronous train step
        return learn(act, loss_fn, optimizer, lr_schedule, params, opt_state,
                     traj, last_obs, step)

    if not fused_publish:
        return _update

    def learner_step(params, opt_state, traj, last_obs, step, publish_dst):
        del publish_dst  # donation target only: its buffers back `published`
        params, opt_state, metrics = _update(
            params, opt_state, traj, last_obs, step
        )
        # bitwise snapshot for the actors — a copy op, so donating `params`
        # at the jit boundary can never invalidate what actors read
        published = jax.tree_util.tree_map(lambda a: a.copy(), params)
        return params, opt_state, published, metrics

    return learner_step


def make_sharded_learner_step(agent, optimizer, lr_schedule, mesh,
                              rho_bar: float = 1.0, c_bar: float = 1.0,
                              fused_publish: bool = True) -> Callable:
    """The mesh-plane twin of ``make_learner_step``: jitted with shardings.

    ``mesh`` is a 1-axis ``("data",)`` rollout mesh
    (``repro.launch.mesh.make_rollout_mesh``). Inputs arrive pre-sharded —
    the trajectory/bootstrap batch env-axis-partitioned over ``"data"``
    (``MeshTrajectoryRing.get`` assembles exactly that), params/opt
    state/publish buffer replicated — and every output is pinned replicated,
    which is what makes XLA all-reduce the per-device partial gradients
    across the data axis inside the step. Donation semantics are inherited
    unchanged from the flat step: with ``fused_publish`` (the orchestrator's
    configuration) params, opt state and the stale publish target are
    donated and alias their shard-identical outputs.

    Returns the *jitted* callable (unlike ``make_learner_step``, which
    leaves jitting to the caller): the sharding spec is part of the step's
    identity here.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    step = make_learner_step(agent, optimizer, lr_schedule, rho_bar=rho_bar,
                             c_bar=c_bar, fused_publish=fused_publish)
    replicated = NamedSharding(mesh, P())
    # a single sharding broadcasts over the whole output tree: new params,
    # new opt state, published snapshot and the metric scalars are all
    # replicated (the batch means/sums inside the loss already force the
    # cross-device reduction)
    return jax.jit(
        step,
        out_shardings=replicated,
        donate_argnums=(0, 1, 5) if fused_publish else (0, 1),
    )
