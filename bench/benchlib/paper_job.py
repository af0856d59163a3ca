"""The paper's Atari job as the program builds it, from a configuration
file: the network, the game, the PAAC settings, and what the program will
really run, in the file's own terms, for the harness to hold against the
file."""
from __future__ import annotations

import inspect


def _program_settings(cfg, hp) -> dict:
    """What the program will run, in the configuration file's terms."""
    from repro.optim import make_optimizer

    defaults = inspect.signature(make_optimizer).parameters
    return {
        "obs_shape": list(cfg.obs_shape),
        "convs": [list(c) for c in cfg.cnn_spec],
        "dense": cfg.cnn_dense,
        "num_actions": cfg.actions(),
        "param_dtype": cfg.param_dtype,
        "compute_dtype": cfg.compute_dtype,
        "gamma": hp.gamma,
        "entropy_beta": hp.entropy_beta,
        "value_coef": hp.value_coef,
        "optimizer": {"kind": "rmsprop",
                      "decay": defaults["decay"].default,
                      "eps": defaults["eps"].default,
                      "clip_norm": defaults["clip_norm"].default},
    }


def paper_job(config: dict, n_envs: int, t_max: int):
    """The configuration's network on ``FrameStack(AtariLike(n_envs), 4)``,
    as the repository's paper example builds it. Returns (env, agent,
    program settings)."""
    from repro.configs import get_config
    from repro.core.agents import PAACAgent, PAACConfig
    from repro.envs import AtariLike, FrameStack

    env = FrameStack(AtariLike(n_envs), n=config["obs_shape"][-1])
    cfg = get_config(config["program_config"]).replace(
        obs_shape=env.obs_shape, num_actions=env.num_actions)
    hp = PAACConfig(gamma=config["gamma"],
                    entropy_beta=config["entropy_beta"],
                    t_max=t_max, value_coef=config["value_coef"])
    return env, PAACAgent(cfg, hp), _program_settings(cfg, hp)
