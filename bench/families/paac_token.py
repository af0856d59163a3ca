"""Family ``paac_token``: DeepSeek-V2 token policies (MLA, YaRN, routed and
shared experts held as one chip's share) on the repository's token game,
``TokenEnv``.

A family module gives, under these names:

* ``job(config, n_envs, t_max) -> (env, agent, settings)``: the program's
  environment and agent for the configuration, and what the program will
  really run in the file's own terms (held against the file);
* ``flops_per_timestep(config, t_max)``: the model FLOPs one environment
  timestep requires, behind ``mfu`` (``benchlib/token_flops.py``);
* ``train(config, seed, *, n_envs, lanes, t_max, lr, steps, dtype, fault,
  lane_keys_per_step)`` and ``FAULTS``: the plain reference behind
  ``correct`` (``reference/paac_token.py``), which imports nothing of the
  program. Its ``dtype`` other than float32 is the control: every matmul
  operand rounded to float8_e4m3fn, the precision below the
  configuration's bfloat16.

Building the job starts keeping the program's run counters
(``benchlib/counters.py``), which the expert-layer reader uses.
"""
from __future__ import annotations

import inspect

from benchlib import counters
from benchlib.token_flops import flops_per_timestep
from reference.paac_token import FAULTS, train

__all__ = ["FAULTS", "flops_per_timestep", "job", "train"]


def _program_settings(cfg, hp, env) -> dict:
    """What the program will run, in the configuration file's terms."""
    from repro.optim import make_optimizer

    defaults = inspect.signature(make_optimizer).parameters
    return {
        "hidden_size": cfg.d_model,
        "hidden_act": {"swiglu": "silu"}.get(cfg.mlp, cfg.mlp),
        "num_hidden_layers": cfg.num_layers,
        "first_k_dense_replace": cfg.first_dense_layers,
        "intermediate_size": cfg.dense_d_ff,
        "vocab_size": cfg.vocab_size,
        "tie_word_embeddings": cfg.tie_policy_head,
        "num_attention_heads": cfg.num_heads,
        "q_lora_rank": cfg.q_lora_rank or None,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim,
        "v_head_dim": cfg.v_head_dim,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "yarn", "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original_max_pos,
            "beta_fast": cfg.yarn_beta_fast, "beta_slow": cfg.yarn_beta_slow,
            "mscale": cfg.yarn_mscale, "mscale_all_dim": cfg.yarn_mscale_all_dim,
        },
        "rms_norm_eps": cfg.norm_eps,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_routed_experts_total": cfg.num_experts,
        "n_routed_experts": cfg.experts_held,
        "experts_held_from": cfg.experts_held_from,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "n_shared_experts": cfg.num_shared_experts,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "param_dtype": cfg.param_dtype,
        "compute_dtype": cfg.compute_dtype,
        "game": {"ctx": env.ctx, "k": env.k, "horizon": env.horizon},
        "gamma": hp.gamma,
        "entropy_beta": hp.entropy_beta,
        "value_coef": hp.value_coef,
        "optimizer": {"kind": "rmsprop",
                      "decay": defaults["decay"].default,
                      "eps": defaults["eps"].default,
                      "clip_norm": defaults["clip_norm"].default},
    }


def job(config: dict, n_envs: int, t_max: int):
    """The configuration's policy on ``TokenEnv(n_envs)`` over its
    vocabulary slice: the registered architecture cut to the file's depth,
    held experts and vocabulary. Returns (env, agent, program settings)."""
    from repro.configs import get_config
    from repro.core.agents import PAACAgent, PAACConfig
    from repro.envs import TokenEnv

    counters.listen()
    game = config["game"]
    env = TokenEnv(n_envs, vocab=config["vocab_size"], ctx=game["ctx"],
                   k=game["k"], horizon=game["horizon"])
    cfg = get_config(config["program_config"]).replace(
        num_layers=config["num_hidden_layers"],
        vocab_size=config["vocab_size"],
        experts_held=config["n_routed_experts"],
        experts_held_from=config["experts_held_from"])
    hp = PAACConfig(gamma=config["gamma"],
                    entropy_beta=config["entropy_beta"],
                    t_max=t_max, value_coef=config["value_coef"])
    return env, PAACAgent(cfg, hp), _program_settings(cfg, hp, env)
