"""Drive the mesh cell's runs on four virtual CPU devices, sound and with
each fault planted, and print one JSON line of verdicts.

Run by ``test_bench_mesh_check.py`` in a process of its own, because the
device count is fixed when JAX starts:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/mesh_faults.py
"""
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

SEED = 2**31 + 13


def _small():
    from benchlib import cells

    cell = cells.load_cell("nature-mesh4-e32x4")
    wl = dict(cell.workload, n_envs=2, t_max=3)
    wl["lr"] = cell.config["lr_per_env"] * 2 * wl["lanes"]
    return cell._replace(workload=wl)


def _no_exchange(t_max: int):
    """Each chip learns from its own lane's shard alone, as without the
    gradient all-reduce; the parameters read back are chip 0's. The losses
    see the time-major (t_max * E) batch and keep lane 0's environments."""
    from repro.pipeline import learner

    losses = learner.paac_losses

    def local(logits, values, actions, returns, *rest, **kw):
        def lane0(x):
            x = x.reshape((t_max, -1) + x.shape[1:])
            return x[:, : x.shape[1] // 4].reshape((-1,) + x.shape[2:])
        return losses(lane0(logits), lane0(values), lane0(actions),
                      lane0(returns), *rest, **kw)

    learner.paac_losses = local
    return losses


def _unchanged_state(entry):
    import jax

    prl = entry.prl
    step = prl._update_step

    def broken(params, opt_state, traj, last_obs, step_arr, publish_dst):
        copy = lambda t: jax.tree_util.tree_map(lambda a: a.copy(), t)
        _, _, _, metrics = step(copy(params), copy(opt_state), traj, last_obs,
                                step_arr, publish_dst)
        return params, opt_state, copy(params), metrics

    prl._update_step = broken


def main() -> int:
    import jax

    from benchlib import cells, harness

    cell = _small()
    names = cells.metrics_for(cells.spec(), cell.name, False)
    devices = jax.devices()[:4]

    def run(plant=None):
        return harness.run_cell(cell, SEED, 0.2, False, devices,
                                time.perf_counter(), names, plant=plant)

    out = {"devices": len(jax.devices()), "sound": run()["correct"]}
    out["unchanged_state"] = run(_unchanged_state)["correct"]
    from repro.pipeline import learner

    losses = _no_exchange(cell.workload["t_max"])
    try:
        out["no_exchange"] = run()["correct"]
    finally:
        learner.paac_losses = losses
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
