"""Entry ``mesh``: data-parallel PAAC over the chips of one host, through
``repro.pipeline.PipelinedRL`` on its mesh rollout plane.

One actor lane per chip collects ``n_envs`` environments for t_max steps
on its own chip; the lanes' rollouts are reassembled into one batch sharded
over the chips, and the sharded learner step all-reduces the per-chip
gradients. Depth-1 lockstep with infinite V-trace clips: every update
learns from rollouts acted with the parameters it updates (on-policy,
staleness 0). One ``run(n)`` is n learner updates; the cell's measured
window is one such call. Each lane's environment, and the agent, are the
configuration's family's ``job``.
"""
from __future__ import annotations

from benchlib import cells

# the learner waits on a trajectory queue (RunResult.learner_idle_s)
LEARNER_QUEUE = True


def reference_layout(workload: dict) -> dict:
    """How the family's reference ``train`` follows this entry: every
    ``run()`` call draws one acting key per lane from the carried key."""
    return {"n_envs": workload["n_envs"], "lanes": workload["lanes"],
            "t_max": workload["t_max"], "lr": workload["lr"],
            "lane_keys_per_step": True}


class Entry:
    def __init__(self, config: dict, workload: dict, seed: int, devices):
        from repro.configs import PipelineConfig
        from repro.optim import constant
        from repro.pipeline import PipelinedRL

        lanes = workload["lanes"]
        if lanes != len(devices):
            raise ValueError(f"{lanes} lanes need {lanes} chips, "
                             f"got {len(devices)}")
        job = cells.family(config["family"]).job
        jobs = [job(config, workload["n_envs"], workload["t_max"])
                for _ in range(lanes)]
        agent, self.settings = jobs[0][1], jobs[0][2]
        inf = float("inf")
        pipe = PipelineConfig(queue_depth=workload["queue_depth"],
                              lockstep=True, rollout_plane="mesh",
                              mesh_shape=lanes, num_actors=lanes,
                              rho_bar=inf, c_bar=inf)
        self.timesteps_per_update = lanes * workload["n_envs"] * workload["t_max"]
        self.prl = PipelinedRL([env for env, _, _ in jobs], agent,
                               lr_schedule=constant(workload["lr"]),
                               seed=seed, pipeline=pipe)

    def run(self, n: int):
        return self.prl.run(n)

    def params(self):
        return self.prl.params

    def opt_state(self):
        return self.prl.opt_state

    def close(self) -> None:
        if self.prl is not None:
            self.prl.close()
            self.prl = None
