"""Plain PAAC training, written from the paper's equations.

Clemente et al. 2017 (arXiv:1705.04862), Algorithm 1 with the networks and
settings of its section 5.1, in straightforward ``jax.numpy``:

* the policy/value network: convolutions then a dense layer, each with a
  ReLU, and two linear heads (a softmax policy and one value);
* acting: one batched forward for every environment, an independent
  categorical draw per environment, then the game's step;
* n-step returns R_t = r_t + gamma (1 - done_t) R_{t+1}, bootstrapped from
  V(s_{t_max+1}) under the same parameters;
* the losses of equations (10) and (11), averaged over n_e * t_max samples:
  policy -mean(A log pi(a|s)) with A = R - V held constant, entropy bonus
  beta, value coefficient ``value_coef`` on mean((R - V)^2);
* global-norm clipping, then RMSProp with one shared second-moment
  accumulator.

``dtype`` float32 computes every matmul and convolution at full float32
precision (``Precision.HIGHEST``), which is the oracle. Any other dtype runs
parameters, activations, gradients and optimizer state in that dtype at the
default precision: that is the lower-precision control.

Weights are drawn from the seed by the network's published initialisation
(normal with standard deviation 1/sqrt(fan_in), zero biases), in the same
key order as the system's configuration, so a seed gives the same network.
Nothing here imports the system under test.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from reference.atari import StackedGame

FAULTS = ("half_batch", "one_lane", "altered_action")


class Net(NamedTuple):
    """The widths of one configuration file, hashable for ``jax.jit``."""
    obs_shape: tuple
    convs: tuple  # ((features, kernel, stride), ...)
    dense: int
    actions: int

    @classmethod
    def from_config(cls, config: dict) -> "Net":
        return cls(tuple(config["obs_shape"]),
                   tuple(tuple(c) for c in config["convs"]),
                   int(config["dense"]), int(config["num_actions"]))


class Hyper(NamedTuple):
    gamma: float
    beta: float
    value_coef: float
    decay: float
    eps: float
    clip: float
    lr: float

    @classmethod
    def from_config(cls, config: dict, lr: float) -> "Hyper":
        opt = config["optimizer"]
        return cls(config["gamma"], config["entropy_beta"],
                   config["value_coef"], opt["decay"], opt["eps"],
                   opt["clip_norm"], lr)


def init_params(key, net: Net, dtype=jnp.float32) -> Dict:
    k_trunk, k_heads = jax.random.split(key)
    ks = jax.random.split(k_trunk, len(net.convs) + 1)
    convs, ch, size = [], net.obs_shape[-1], net.obs_shape[0]
    for k, (feat, kern, stride) in zip(ks, net.convs):
        std = 1.0 / math.sqrt(kern * kern * ch)
        w = jax.random.normal(k, (kern, kern, ch, feat)) * std
        convs.append({"w": w.astype(dtype), "b": jnp.zeros((feat,), dtype)})
        ch, size = feat, (size - kern) // stride + 1
    flat = size * size * ch
    dense = {"w": (jax.random.normal(ks[-1], (flat, net.dense))
                   * (1.0 / math.sqrt(flat))).astype(dtype),
             "b": jnp.zeros((net.dense,), dtype)}
    k_pol, k_val = jax.random.split(k_heads)
    heads = {
        "policy": {"w": (jax.random.normal(k_pol, (net.dense, net.actions))
                         * (1.0 / math.sqrt(net.dense))).astype(dtype)},
        "value": {"w": (jax.random.normal(k_val, (net.dense, 1))
                        * (1.0 / math.sqrt(net.dense))).astype(dtype),
                  "b": jnp.zeros((1,), dtype)},
    }
    return {"trunk": {"convs": convs, "dense": dense}, "heads": heads}


def _precision(dtype):
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def forward(params, net: Net, obs):
    """obs (B, 84, 84, C) -> logits (B, A), value (B,)."""
    dtype = params["trunk"]["dense"]["w"].dtype
    prec = _precision(dtype)
    x = obs.astype(dtype)
    for layer, (_, _, stride) in zip(params["trunk"]["convs"], net.convs):
        x = lax.conv_general_dilated(
            x, layer["w"], (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
        x = jax.nn.relu(x + layer["b"])
    x = x.reshape(x.shape[0], -1)
    d = params["trunk"]["dense"]
    h = jax.nn.relu(jnp.dot(x, d["w"], precision=prec) + d["b"])
    heads = params["heads"]
    logits = jnp.dot(h, heads["policy"]["w"], precision=prec)
    value = jnp.dot(h, heads["value"]["w"], precision=prec) + heads["value"]["b"]
    return logits, value[:, 0]


def collect(params, net: Net, game: StackedGame, state, key, t_max: int,
            fault: Optional[str] = None):
    """t_max steps of every game; returns (state, key, obs, actions,
    rewards, dones) with time-major (T, E, ...) arrays."""
    obs_t, act_t, rew_t, done_t = [], [], [], []
    for _ in range(t_max):
        key, k_act, k_env = jax.random.split(key, 3)
        obs = game.observe(state)
        logits, _ = forward(params, net, obs)
        action = jax.random.categorical(k_act, logits)
        if fault == "altered_action":
            action = (action + 1) % net.actions
        state, _, reward, done = game.step(state, action, k_env)
        obs_t.append(obs)
        act_t.append(action)
        rew_t.append(reward)
        done_t.append(done)
    return (state, key, jnp.stack(obs_t), jnp.stack(act_t), jnp.stack(rew_t),
            jnp.stack(done_t))


def n_step_returns(rewards, dones, bootstrap, gamma):
    """(T, E) rewards/dones, (E,) bootstrap -> (T, E) returns."""
    ret, out = bootstrap, []
    for t in range(rewards.shape[0] - 1, -1, -1):
        ret = rewards[t] + gamma * (1.0 - dones[t].astype(ret.dtype)) * ret
        out.append(ret)
    return jnp.stack(out[::-1])


def loss_fn(params, net: Net, hp: Hyper, obs, actions, returns):
    T, E = actions.shape
    logits, values = forward(params, net, obs.reshape((T * E,) + obs.shape[2:]))
    logp = jax.nn.log_softmax(logits)
    a = actions.reshape(T * E)
    logp_a = jnp.take_along_axis(logp, a[:, None], axis=1)[:, 0]
    r = returns.reshape(T * E).astype(values.dtype)
    adv = lax.stop_gradient(r - values)
    policy = -jnp.mean(adv * logp_a)
    entropy = -jnp.mean(jnp.sum(jnp.exp(logp) * logp, axis=-1))
    value = jnp.mean(jnp.square(r - values))
    return policy - hp.beta * entropy + hp.value_coef * value


def update(params, sq, net: Net, hp: Hyper, obs, actions, rewards, dones,
           last_obs):
    """Returns (params, sq, loss, clipped grads) for one batch."""
    _, boot = forward(params, net, last_obs)
    returns = n_step_returns(rewards.astype(boot.dtype), dones,
                             lax.stop_gradient(boot), hp.gamma)
    loss, grads = jax.value_and_grad(loss_fn)(params, net, hp, obs, actions,
                                              returns)
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))
    scale = jnp.minimum(1.0, hp.clip / jnp.maximum(norm, 1e-9))
    grads = jax.tree_util.tree_map(lambda g: (g * scale).astype(g.dtype), grads)
    sq = jax.tree_util.tree_map(
        lambda s, g: hp.decay * s + (1.0 - hp.decay) * jnp.square(g), sq, grads)
    params = jax.tree_util.tree_map(
        lambda p, g, s: p - hp.lr * g / (jnp.sqrt(s) + hp.eps), params, grads, sq)
    return params, sq, loss, grads


def _take(fault, lanes, per_lane, arrays, last_obs):
    """The part of the batch that a fault plant learns from."""
    E = arrays[1].shape[1]
    keep = {"half_batch": E // 2, "one_lane": per_lane}.get(fault, E)
    return [x[:, :keep] for x in arrays], last_obs[:keep]


@partial(jax.jit, static_argnames=("net", "hp", "game", "t_max", "lanes",
                                   "fault"))
def _step(params, sq, states, keys, net, hp, game, t_max, lanes, fault):
    parts = [collect(params, net, game, s, k, t_max, fault)
             for s, k in zip(states, keys)]
    states = [p[0] for p in parts]
    out_keys = [p[1] for p in parts]
    arrays = [jnp.concatenate([p[i] for p in parts], axis=1)
              for i in range(2, 6)]
    last_obs = jnp.concatenate([game.observe(s) for s in states], axis=0)
    arrays, last_obs = _take(fault, lanes, game.n, arrays, last_obs)
    params, sq, loss, grads = update(params, sq, net, hp, *arrays, last_obs)
    return params, sq, states, out_keys, loss, grads


def train(config: dict, seed: int, *, n_envs: int, lanes: int, t_max: int,
          lr: float, steps: int = 3, dtype=jnp.float32,
          fault: Optional[str] = None, lane_keys_per_step: bool = False):
    """Follow ``steps`` PAAC iterations from ``seed``.

    ``lanes`` groups of ``n_envs`` games each learn in one batch. With
    ``lane_keys_per_step`` every iteration draws fresh acting keys, one per
    lane, from the carried key (``split(key, lanes + 1)``: the first is
    carried on); otherwise the single lane carries its key from step to
    step. Returns a dict with ``losses`` (per step), ``grads`` (the first
    step's clipped gradient), ``params0`` and ``params`` (after ``steps``).
    """
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    net = Net.from_config(config)
    hp = Hyper.from_config(config, lr)
    game = StackedGame(n_envs, stack=config["obs_shape"][-1])
    key, k_init, k_env = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = init_params(k_init, net, dtype)
    params0 = params
    sq = jax.tree_util.tree_map(jnp.zeros_like, params)
    if lanes == 1:
        states = [game.reset(k_env)]
    else:
        states = [game.reset(k) for k in jax.random.split(k_env, lanes)]
    losses: List[float] = []
    grads0 = None
    lane_keys = [key]
    for _ in range(steps):
        if lane_keys_per_step:
            ks = jax.random.split(key, lanes + 1)
            key, lane_keys = ks[0], list(ks[1:])
        params, sq, states, lane_keys, loss, grads = _step(
            params, sq, states, lane_keys, net, hp, game, t_max, lanes, fault)
        losses.append(float(loss))
        if grads0 is None:
            grads0 = grads
    return {"losses": losses, "grads": grads0, "params0": params0,
            "params": params}
