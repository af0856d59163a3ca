"""The master loop — paper Algorithm 1 lines 4–10.

One ``lax.scan`` iteration = one framework timestep:

  1. the *master* evaluates the policy for ALL ``n_e`` environments in one
     batched forward (line 5-6),
  2. actions are sampled per environment (the policy "may be sampled
     differently for each environment" — independent categorical draws),
  3. the *workers* apply all actions in parallel (line 7-10) — here the
     vmapped env step fused into the same XLA program.

Because environments are JAX-native, acting, stepping and (in the agents)
learning compile into a single device program per PAAC iteration. The
scan runs under the ``rollout`` name scope, its body's policy call under
``policy.forward`` and the environment step (auto-reset and frame stacking
included) under ``env.step``: the names every op of the program carries in
its HLO ``op_name``, by which a device trace splits the step's time.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Transition(NamedTuple):
    obs: jnp.ndarray  # (T, E, *obs_shape)
    action: jnp.ndarray  # (T, E)
    reward: jnp.ndarray  # (T, E)
    done: jnp.ndarray  # (T, E)
    value: jnp.ndarray  # (T, E) — V(s_t) computed during acting (line 6)
    logp: jnp.ndarray  # (T, E) — log π(a_t|s_t) at acting time


def rollout(
    act_fn: Callable,  # (params, obs) -> (logits (E,A), value (E,))
    env,
    params,
    env_state,
    obs,
    key,
    t_max: int,
):
    """Collect t_max steps from all n_e environments.

    Returns (env_state, last_obs, key, traj: Transition [time-major]).
    """

    def step(carry, _):
        env_state, obs, key = carry
        key, k_act, k_env = jax.random.split(key, 3)
        with jax.named_scope("policy.forward"):
            logits, value = act_fn(params, obs)
            action = jax.random.categorical(k_act, logits)
            # behaviour log-prob from the sampled action's logit alone:
            # log π(a|s) = logits[a] − logsumexp(logits). Gathering first
            # keeps the acting scan from materializing the full (E, A)
            # log_softmax matrix when only one column per row is ever read.
            action_logit = jnp.take_along_axis(logits, action[:, None],
                                               axis=1)[:, 0]
            logp = action_logit - jax.scipy.special.logsumexp(logits, axis=1)
        with jax.named_scope("env.step"):
            env_state, next_obs, reward, done = env.step(env_state, action,
                                                         k_env)
        tr = Transition(obs, action, reward, done, value, logp)
        return (env_state, next_obs, key), tr

    with jax.named_scope("rollout"):
        (env_state, obs, key), traj = jax.lax.scan(
            step, (env_state, obs, key), None, length=t_max
        )
    return env_state, obs, key, traj


def make_collect_fn(act_fn: Callable, env, t_max: int) -> Callable:
    """Standalone jittable rollout collector.

    Returns ``collect(params, env_state, obs, key) -> (env_state, last_obs,
    key, traj)`` — exactly the acting half of Algorithm 1, detached from the
    learning half so an asynchronous actor (``repro.pipeline``) can run it on
    its own thread while the learner consumes the previous trajectory. The
    key evolution is identical to the fused train step's, so a lock-stepped
    pipeline reproduces the synchronous trajectory stream bit-for-bit.
    """

    def collect(params, env_state, obs, key):
        return rollout(act_fn, env, params, env_state, obs, key, t_max)

    return collect
