"""Deterministic emulations of the baselines the paper compares against.

The paper's argument (§1, §3) is that PAAC avoids two specific failure
modes, which we reproduce *as controlled pathologies* so benchmarks can
compare convergence:

* **A3C-sim** — stale gradients: gradients are computed w.r.t. a parameter
  copy that lags ``delay`` updates behind (gradients "computed w.r.t. stale
  parameters while updates applied to a new parameter set", fn.1). Updates
  remain sequential (we do not model lock-free write races, which are not
  representable deterministically — noted in DESIGN.md).
* **GA3C-sim** — policy lag: actions are selected with a parameter copy that
  lags ``lag`` updates behind the learner (GA3C's queue between predictor
  and trainer), so learning is slightly off-policy exactly as described in
  Babaeizadeh et al. 2016.

Setting delay/lag = 0 recovers exact PAAC — giving a clean ablation axis.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.agents.paac import (
    PAACAgent,
    PAACConfig,
    paac_losses,
    trajectory_logits_values,
)
from repro.core.returns import n_step_returns
from repro.core.rollout import rollout


class LaggedConfig(NamedTuple):
    gamma: float = 0.99
    entropy_beta: float = 0.01
    t_max: int = 5
    value_coef: float = 0.5
    delay: int = 4  # parameter-copy staleness in updates


class LaggedPAACAgent(PAACAgent):
    """A2C with a lagging parameter copy.

    mode="grad"  -> A3C-sim  (gradient computed at stale params)
    mode="act"   -> GA3C-sim (actions sampled from stale params)
    """

    def __init__(self, cfg, hp: LaggedConfig = LaggedConfig(), mode: str = "grad"):
        super().__init__(cfg, PAACConfig(hp.gamma, hp.entropy_beta, hp.t_max, hp.value_coef))
        assert mode in ("grad", "act")
        self.lag_hp = hp
        self.mode = mode

    def init_state(self, params):
        return {"stale": params, "since": jnp.zeros((), jnp.int32)}

    def make_train_step(self, env, optimizer, lr_schedule):
        cfg, hp = self.cfg, self.lag_hp
        act = self.act_fn()
        mode = self.mode

        def loss_fn(params, traj, bootstrap):
            T, E = traj.action.shape
            logits, values, _ = trajectory_logits_values(params, cfg, traj)
            returns = n_step_returns(traj.reward.T, traj.done.T, bootstrap, hp.gamma)
            return paac_losses(
                logits,
                values,
                traj.action.reshape(T * E),
                returns.T.reshape(T * E),
                hp.entropy_beta,
                hp.value_coef,
            )

        def train_step(params, opt_state, agent_state, env_state, obs, key, step):
            stale = agent_state["stale"]
            acting_params = stale if mode == "act" else params
            env_state, last_obs, key, traj = rollout(
                act, env, acting_params, env_state, obs, key, hp.t_max
            )
            _, bootstrap = act(acting_params, last_obs)
            bootstrap = jax.lax.stop_gradient(bootstrap)
            grad_params = stale if mode == "grad" else params
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                grad_params, traj, bootstrap
            )
            # the update is applied to the CURRENT params (the inconsistency)
            lr = lr_schedule(step)
            params, opt_state = optimizer.update(grads, opt_state, params, lr)

            since = agent_state["since"] + 1
            refresh = since >= hp.delay
            stale = jax.tree_util.tree_map(
                lambda s, p: jnp.where(refresh, p, s), stale, params
            )
            agent_state = {"stale": stale, "since": jnp.where(refresh, 0, since)}
            metrics = dict(metrics)
            metrics["loss"] = loss
            metrics["reward_sum"] = jnp.sum(traj.reward)
            metrics["episodes"] = jnp.sum(traj.done)
            return params, opt_state, agent_state, env_state, last_obs, key, metrics

        return train_step
