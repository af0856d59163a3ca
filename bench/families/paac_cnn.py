"""Family ``paac_cnn``: the paper's convolutional PAAC networks
(``obs_shape``, ``convs``, ``dense``, ``num_actions``) on the pixel game.

A family module gives, under these names:

* ``job(config, n_envs, t_max) -> (env, agent, settings)``: the program's
  environment and agent for the configuration, and what the program will
  really run in the file's own terms (held against the file);
* ``flops_per_timestep(config, t_max)``: the model FLOPs one environment
  timestep requires, behind ``mfu``;
* ``train(config, seed, *, n_envs, lanes, t_max, lr, steps, dtype, fault,
  lane_keys_per_step)`` and ``FAULTS``: the plain reference behind
  ``correct``, which imports nothing of the program.
"""
from benchlib.flops import flops_per_timestep
from benchlib.paper_job import paper_job as job
from reference.paac import FAULTS, train

__all__ = ["FAULTS", "flops_per_timestep", "job", "train"]
