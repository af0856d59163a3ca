"""Parallel Advantage Actor-Critic — the paper's demonstrated instance (§4).

Losses are the paper's equations (10)–(11):

  ∇θ  ≈ 1/(n_e·t_max) Σ_e Σ_t (R_t − V(s_t)) ∇ log π(a_t|s_t) + β ∇ H(π)
  ∇θv ≈ ∇ 1/(n_e·t_max) Σ_e Σ_t (R_t − V(s_t))²

with the shared-trunk two-headed network of §5.1, RMSProp with shared
statistics and global-norm clipping at 40. One ``train_step`` call is one
full Algorithm-1 iteration (rollout → returns → synchronous update) as a
single compiled program.

Two train-step flavours:
* ``make_train_step``      — environment-in-the-loop (CNN/vector envs).
* ``make_llm_train_step``  — trajectory-batch form for token environments /
  the assigned architectures: the batch is {tokens (B,T+1), rewards (B,T),
  dones (B,T)} and one sequence is one actor's trajectory. This is the form
  lowered in the multi-pod dry-run (train_4k).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.agents.base import Agent
from repro.core.returns import n_step_returns
from repro.core.rollout import rollout
from repro.models import policy_apply, policy_apply_last


# the train step's metric that counts work (``ParallelRL.run`` publishes its
# run total): assignments to held experts in the learner's trajectory pass,
# summed over layers
HELD_LEARNER = "count.moe_held_learner"


class PAACConfig(NamedTuple):
    gamma: float = 0.99
    entropy_beta: float = 0.01
    t_max: int = 5
    value_coef: float = 0.5
    moe_aux_coef: float = 0.01


def paac_losses(logits, values, actions, returns, beta, value_coef,
                weights=None):
    """Equations (10) and (11), averaged over the n_e·t_max batch.

    logits: (N, A) fp32; values/returns: (N,); actions: (N,) int.
    weights: optional (N,) per-sample importance weights (stop-gradient),
    used by the asynchronous pipeline's staleness correction; ``None`` is the
    paper's on-policy case (all ones).
    """
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[:, None], axis=1)[:, 0]
    adv = jax.lax.stop_gradient(returns - values)
    if weights is None:
        w = 1.0
    else:
        w = jax.lax.stop_gradient(weights)
    policy_loss = -jnp.mean(w * adv * logp_a)
    entropy = -jnp.mean(jnp.sum(jnp.exp(logp) * logp, axis=-1))
    value_loss = jnp.mean(w * jnp.square(returns - values))
    total = policy_loss - beta * entropy + value_coef * value_loss
    return total, {
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": entropy,
    }


def policy_heads(params, cfg, obs, *, train: bool = False):
    """``(logits (B, A), values (B,), aux)``: CNN policies over the whole
    observation, token policies at each context's last position only."""
    if cfg.family == "cnn":
        return policy_apply(params, cfg, obs)
    return policy_apply_last(params, cfg, obs, train=train)


def trajectory_logits_values(params, cfg, traj):
    """One batched learning-pass forward over a time-major ``Transition``.

    Returns ``(logits (N, A), values (N,), aux)`` flattened time-major to
    the n_e·t_max batch (index = t·n_e + e), with the model's aux dict. The
    pipelined V-trace learner uses this directly (it builds its own targets
    from the importance ratios).
    """
    T, E = traj.action.shape
    obs = traj.obs.reshape((T * E,) + traj.obs.shape[2:])
    return policy_heads(params, cfg, obs, train=True)


def trajectory_forward(params, cfg, hp, traj, bootstrap):
    """Recompute the learning-pass forward over a time-major ``Transition``.

    Shared by the fused synchronous train step and the pipelined learner
    (``repro.pipeline.learner``) so the two backends optimize the same
    quantities. Returns ``(logits, values, actions, returns, aux)``
    flattened to the n_e·t_max batch the paper's equations average over,
    with the model's aux dict.
    """
    T, E = traj.action.shape
    logits, values, aux = trajectory_logits_values(params, cfg, traj)
    returns = n_step_returns(
        traj.reward.T, traj.done.T, bootstrap, hp.gamma
    )  # (E, T)
    returns = returns.T.reshape(T * E)
    actions = traj.action.reshape(T * E)
    return logits, values, actions, returns, aux


def learn(act, loss_fn, optimizer, lr_schedule, params, opt_state, traj,
          last_obs, step):
    """The learning half of one iteration (Algorithm 1 lines 11-19).

    Bootstraps V(s_{tmax+1}) from ``last_obs``, takes ``loss_fn(params,
    traj, bootstrap) -> (loss, metrics)``'s gradient and applies one
    optimizer step. Shared by the fused train step and the pipelined
    learner (``repro.pipeline.learner``). Runs under the ``learner`` name
    scope, with ``loss_grad`` around the bootstrap forward and the gradient
    (the returns included) and ``optimizer`` around the update.
    """
    with jax.named_scope("learner"):
        with jax.named_scope("loss_grad"):
            _, bootstrap = act(params, last_obs)
            bootstrap = jax.lax.stop_gradient(bootstrap)
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, traj, bootstrap)
        lr = lr_schedule(step)
        with jax.named_scope("optimizer"):
            params, opt_state = optimizer.update(grads, opt_state, params, lr)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["reward_sum"] = jnp.sum(traj.reward)
        metrics["episodes"] = jnp.sum(traj.done)
    return params, opt_state, metrics


class PAACAgent(Agent):
    """The paper's agent. model cfg + hyperparameters -> jittable steps."""

    on_policy = True

    def __init__(self, cfg, hp: PAACConfig = PAACConfig()):
        self.cfg = cfg
        self.hp = hp

    # -- acting --------------------------------------------------------------
    def act_fn(self):
        cfg = self.cfg

        def fn(params, obs):
            logits, value, _ = policy_heads(params, cfg, obs)
            return logits, value

        return fn

    # -- env-in-the-loop train step (Algorithm 1) ----------------------------
    def make_train_step(self, env, optimizer, lr_schedule):
        cfg, hp = self.cfg, self.hp
        act = self.act_fn()

        def loss_fn(params, traj, bootstrap):
            # recompute forward over the whole n_e·t_max batch (one big
            # batched pass — the paper's batched learning)
            logits, values, actions, returns, aux = trajectory_forward(
                params, cfg, hp, traj, bootstrap
            )
            loss, metrics = paac_losses(
                logits, values, actions, returns, hp.entropy_beta, hp.value_coef
            )
            if "moe_held" in aux:
                metrics[HELD_LEARNER] = aux["moe_held"].astype(jnp.float32)
            return loss, metrics

        def train_step(params, opt_state, env_state, obs, key, step):
            env_state, last_obs, key, traj = rollout(
                act, env, params, env_state, obs, key, hp.t_max
            )
            params, opt_state, metrics = learn(
                act, loss_fn, optimizer, lr_schedule, params, opt_state,
                traj, last_obs, step,
            )
            return params, opt_state, env_state, last_obs, key, metrics

        return train_step

    # -- trajectory-batch train step (token archs; lowered in the dry-run) ---
    def make_llm_train_step(self, optimizer, lr_schedule):
        cfg, hp = self.cfg, self.hp

        def loss_fn(params, batch):
            tokens = batch["tokens"]  # (B, T+1)
            inputs, actions = tokens[:, :-1], tokens[:, 1:]
            prefix = batch.get("prefix", batch.get("frames"))
            logits, values, aux = policy_apply(
                params, cfg, inputs, prefix, train=True
            )
            if cfg.prefix_len:  # score text positions only (vlm)
                logits = logits[:, cfg.prefix_len:]
                values = values[:, cfg.prefix_len:]
            B, T = actions.shape
            bootstrap = values[:, -1]
            returns = n_step_returns(batch["rewards"], batch["dones"], bootstrap, hp.gamma)
            total, metrics = paac_losses(
                logits.reshape(B * T, -1),
                values.reshape(B * T),
                actions.reshape(B * T),
                returns.reshape(B * T),
                hp.entropy_beta,
                hp.value_coef,
            )
            if "moe_aux" in aux:
                total = total + hp.moe_aux_coef * aux["moe_aux"]
            return total, metrics

        def train_step(params, opt_state, batch, step):
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            lr = lr_schedule(step)
            params, opt_state = optimizer.update(grads, opt_state, params, lr)
            metrics = dict(metrics)
            metrics["loss"] = loss
            return params, opt_state, metrics

        return train_step
