"""PPO inside the PAAC framework — a beyond-paper extension.

The paper argues its framework hosts "any other reinforcement learning
algorithm" (§4). PAAC-A2C takes one gradient step per batch; PPO's clipped
surrogate allows several epochs over the same synchronous batch — a natural
fit because the framework already stores acting-time log-probs in the
trajectory (rollout.Transition.logp). Uses GAE (returns.gae_advantages).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.agents.base import Agent
from repro.core.returns import gae_advantages
from repro.core.rollout import rollout
from repro.core.agents.paac import policy_heads, trajectory_logits_values


class PPOConfig(NamedTuple):
    gamma: float = 0.99
    lam: float = 0.95
    clip_eps: float = 0.2
    entropy_beta: float = 0.01
    value_coef: float = 0.5
    t_max: int = 16
    epochs: int = 4


class PPOAgent(Agent):
    on_policy = True

    def __init__(self, cfg, hp: PPOConfig = PPOConfig()):
        self.cfg = cfg
        self.hp = hp

    def act_fn(self):
        cfg = self.cfg

        def fn(params, obs):
            logits, value, _ = policy_heads(params, cfg, obs)
            return logits, value

        return fn

    def make_train_step(self, env, optimizer, lr_schedule):
        cfg, hp = self.cfg, self.hp
        act = self.act_fn()

        def loss_fn(params, traj, adv, returns):
            T, E = traj.action.shape
            logits, values, _ = trajectory_logits_values(params, cfg, traj)
            logp_all = jax.nn.log_softmax(logits)
            actions = traj.action.reshape(T * E)
            logp = jnp.take_along_axis(logp_all, actions[:, None], 1)[:, 0]
            ratio = jnp.exp(logp - traj.logp.reshape(T * E))
            a = adv.reshape(T * E)
            a = (a - a.mean()) / (a.std() + 1e-8)
            unclipped = ratio * a
            clipped = jnp.clip(ratio, 1 - hp.clip_eps, 1 + hp.clip_eps) * a
            policy_loss = -jnp.mean(jnp.minimum(unclipped, clipped))
            value_loss = jnp.mean(jnp.square(returns.reshape(T * E) - values))
            entropy = -jnp.mean(jnp.sum(jnp.exp(logp_all) * logp_all, -1))
            total = policy_loss + hp.value_coef * value_loss - hp.entropy_beta * entropy
            return total, {
                "policy_loss": policy_loss,
                "value_loss": value_loss,
                "entropy": entropy,
                "clip_frac": jnp.mean((jnp.abs(ratio - 1) > hp.clip_eps).astype(jnp.float32)),
            }

        def train_step(params, opt_state, env_state, obs, key, step):
            env_state, last_obs, key, traj = rollout(
                act, env, params, env_state, obs, key, hp.t_max
            )
            _, bootstrap = act(params, last_obs)
            adv, returns = gae_advantages(
                traj.reward.T, traj.done.T, traj.value.T,
                jax.lax.stop_gradient(bootstrap), hp.gamma, hp.lam,
            )  # (E, T)
            adv, returns = adv.T, returns.T  # time-major to match traj

            def epoch(carry, _):
                params, opt_state = carry
                (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, traj, adv, returns
                )
                params, opt_state = optimizer.update(
                    grads, opt_state, params, lr_schedule(step)
                )
                return (params, opt_state), (loss, metrics)

            (params, opt_state), (losses, metrics) = jax.lax.scan(
                epoch, (params, opt_state), None, length=hp.epochs
            )
            out = {k: v[-1] for k, v in metrics.items()}
            out["loss"] = losses[-1]
            out["reward_sum"] = jnp.sum(traj.reward)
            out["episodes"] = jnp.sum(traj.done)
            return params, opt_state, env_state, last_obs, key, out

        return train_step
