"""Entry ``sync``: the synchronous PAAC trainer, ``repro.core.ParallelRL``.

One ``run(n)`` is n framework iterations, each one compiled program that
acts for every environment for t_max steps, computes the returns and
applies one RMSProp update. The cell's measured window is one such call.
The environment and agent are the configuration's family's ``job``.
"""
from __future__ import annotations

from benchlib import cells


def reference_layout(workload: dict) -> dict:
    """How the family's reference ``train`` follows this entry: one lane
    that carries its acting key from update to update."""
    return {"n_envs": workload["n_envs"], "lanes": 1,
            "t_max": workload["t_max"], "lr": workload["lr"],
            "lane_keys_per_step": False}


class Entry:
    def __init__(self, config: dict, workload: dict, seed: int, devices):
        from repro.core import ParallelRL
        from repro.optim import constant

        job = cells.family(config["family"]).job
        env, agent, self.settings = job(config, workload["n_envs"],
                                        workload["t_max"])
        self.timesteps_per_update = workload["n_envs"] * workload["t_max"]
        self.rl = ParallelRL(env, agent, optimizer="rmsprop",
                             lr_schedule=constant(workload["lr"]), seed=seed)

    def run(self, n: int):
        return self.rl.run(n)

    def params(self):
        return self.rl.params

    def opt_state(self):
        return self.rl.opt_state

    def close(self) -> None:
        self.rl = None
