"""Bring-up check: the PAAC trainer runs end to end on a TPU.

    python chip_smoke.py              # one chip: the five phases below
    python chip_smoke.py --chips 4    # the mesh plane on four chips, and
                                      # its comparison — no other phase

The model is the paper's own at its published width: ``paac_nature`` (the
Nature CNN) on ``FrameStack(AtariLike(32), n=4)`` with the §5.1 settings
(n_e=32, t_max=5, RMSProp, lr 0.0007·n_e), built as
``examples/paper_atari.py`` builds it, with random weights from a seed.

One chip, in order, in one process:

1. device — the first JAX device must be a TPU; there is no CPU path.
2. synchronous training — ``ParallelRL``: one warm-up ``run()`` then 20
   iterations; the loss is finite, the params moved, and params, optimizer
   state and env state sit on the chip.
3. pipelined training — ``PipelinedRL`` on the device plane, same seed, in
   depth-1 lockstep with infinite clips: its mean metrics match phase 2's
   (``LOCKSTEP_RTOL``). Then a free-running pass: 2 actors, depth 2.
4. process backend — 2 worker subprocesses on the GIL-bound Python
   emulator pool with ``paac_vector``: the learner runs on the chip and
   every worker reports that it acts on the host CPU.
5. kernels — every ``repro.kernels.ops`` entry, compiled, against its
   ``ref.py`` twin at the widths of ``repro.kernels.cases``.

Four chips: ``PipelinedRL`` on the mesh plane, one lane of 32 envs per
chip, depth-1 lockstep. Each lane's obs, env state and RNG key must sit on
its own chip and the learner's params must be replicated over all four;
then one sharded learner update on the 4-way-split batch must match the
same update on one chip over the whole batch (``MESH_RTOL``).

Any failure prints its traceback and exits non-zero. Only when every phase
passed is the last line of standard output
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile seconds and timesteps/s printed on the way are a smoke
reading, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Phase 3: the synchronous train step is one XLA program (collect + update)
# and the lockstep pipeline runs two (collect, then learner), so the chip
# may round them differently. Both see the same trajectories unless a
# sampled action flips on a near-tie; a flip changes the rollout and shows
# as a miss far beyond this bound — a finding to chase, not noise. (On the
# CPU the two are bitwise equal.)
LOCKSTEP_RTOL = 1e-3
# --chips 4: the sharded step all-reduces per-chip partial gradients, so it
# differs from the one-chip step only in f32 summation order.
MESH_RTOL, MESH_ATOL = 1e-4, 1e-5
COMPARED = ("loss", "policy_loss", "value_loss", "entropy", "reward_sum")


def say(msg: str) -> None:
    print(msg, flush=True)


def paper_setup(n_envs: int = 32, t_max: int = 5, arch: str = "paac_nature"):
    """The paper's Atari job, as ``examples/paper_atari.py`` builds it."""
    from repro.configs import get_config
    from repro.core.agents import PAACAgent, PAACConfig
    from repro.envs import AtariLike, FrameStack
    from repro.optim import constant

    env = FrameStack(AtariLike(n_envs), n=4)
    cfg = get_config(arch).replace(obs_shape=env.obs_shape,
                                   num_actions=env.num_actions)
    agent = PAACAgent(cfg, PAACConfig(gamma=0.99, entropy_beta=0.01,
                                      t_max=t_max))
    return env, agent, constant(0.0007 * n_envs)


def _leaves(*trees):
    import jax

    return [l for t in trees for l in jax.tree_util.tree_leaves(t)]


def _assert_on(device, what: str, *trees) -> None:
    for leaf in _leaves(*trees):
        if leaf.devices() != {device}:
            raise AssertionError(f"{what}: a leaf sits on {leaf.devices()}, "
                                 f"not {device}")


def _assert_finite(res, what: str) -> None:
    if not math.isfinite(res.mean_metrics["loss"]):
        raise AssertionError(f"{what}: loss {res.mean_metrics['loss']}")


def check_device(chips: int):
    """Phase 1: the chip JAX sees, or an error naming what it found."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, found {len(devices)}")
    say(f"device: {devices[0].device_kind} x{len(devices)}")
    return devices


def phase_sync(device, n_envs: int = 32, t_max: int = 5, iters: int = 20,
               seed: int = 0) -> dict:
    """Phase 2: ``ParallelRL`` trains the paper's model on ``device``."""
    import numpy as np
    from repro.core import ParallelRL

    env, agent, lr = paper_setup(n_envs, t_max)
    rl = ParallelRL(env, agent, optimizer="rmsprop", lr_schedule=lr,
                    seed=seed)
    before = [np.asarray(l) for l in _leaves(rl.params)]
    t0 = time.perf_counter()
    rl.run(1)  # compiles
    compile_s = time.perf_counter() - t0
    res = rl.run(iters)
    _assert_finite(res, "synchronous")
    if all(np.array_equal(a, np.asarray(b))
           for a, b in zip(before, _leaves(rl.params))):
        raise AssertionError("synchronous: params did not change")
    _assert_on(device, "synchronous params/opt state/env state",
               rl.params, rl.opt_state, rl.env_state)
    say(f"smoke reading, not a benchmark: ParallelRL paac_nature "
        f"n_e={n_envs} t_max={t_max}: first run(1) incl. compile "
        f"{compile_s:.1f} s, {res.timesteps_per_sec:.0f} timesteps/s over "
        f"{iters} iterations")
    return res.mean_metrics


def phase_pipelined(device, sync_metrics: dict, n_envs: int = 32,
                    t_max: int = 5, iters: int = 20, free_iters: int = 6,
                    seed: int = 0, rtol: float = LOCKSTEP_RTOL) -> None:
    """Phase 3: the lockstep pipeline reproduces phase 2; then 2 actors run
    free at depth 2."""
    from repro.configs import PipelineConfig
    from repro.pipeline import PipelinedRL

    inf = float("inf")
    env, agent, lr = paper_setup(n_envs, t_max)
    lock = PipelineConfig(queue_depth=1, lockstep=True, rho_bar=inf,
                          c_bar=inf, rollout_plane="device")
    with PipelinedRL(env, agent, lr_schedule=lr, seed=seed,
                     pipeline=lock) as prl:
        prl.run(1)  # the same warm-up iteration as phase 2
        res = prl.run(iters)
        _assert_on(device, "pipelined params", prl.params)
    worst = 0.0
    for k in COMPARED:
        a, b = sync_metrics[k], res.mean_metrics[k]
        rel = abs(a - b) / max(abs(a), 1e-12)
        worst = max(worst, rel)
        if rel > rtol:
            raise AssertionError(f"lockstep pipeline vs ParallelRL: {k} "
                                 f"{b!r} vs {a!r} (rel {rel:.3g} > {rtol})")
    say(f"lockstep pipeline vs ParallelRL: worst relative difference "
        f"{worst:.3g} over {', '.join(COMPARED)}")

    depth, actors = 2, 2
    free = PipelineConfig(queue_depth=depth, num_actors=actors,
                          rollout_plane="device")
    with PipelinedRL(env, agent, lr_schedule=lr, seed=seed,
                     pipeline=free) as prl:
        res = prl.run(free_iters)
        _assert_on(device, "free-running pipeline params", prl.params)
    _assert_finite(res, "free-running pipeline")
    # the documented bound (repro/pipeline/__init__.py): depth + num_actors
    staleness = res.mean_metrics["staleness"]
    if staleness > depth + actors:
        raise AssertionError(f"free-running pipeline: mean staleness "
                             f"{staleness} > {depth + actors}")
    say(f"free-running pipeline: {actors} actors, depth {depth}, "
        f"{free_iters} updates, mean staleness {staleness:.2f}")


def phase_process(device, n_envs: int = 32, t_max: int = 5,
                  iters: int = 4, seed: int = 0) -> None:
    """Phase 4: worker subprocesses act on the host CPU while the learner
    trains on ``device``."""
    from repro.configs import PipelineConfig, get_config
    from repro.core.agents import PAACAgent, PAACConfig
    from repro.envs import py_bound_spec
    from repro.optim import constant
    from repro.pipeline import PipelinedRL

    # as repro/launch/train.py builds its --actor-backend process job
    spec = py_bound_spec(n_envs, obs_dim=16, spin=0,
                         n_workers=min(8, n_envs))
    cfg = get_config("paac_vector").replace(obs_shape=spec.obs_shape,
                                            num_actions=3)
    agent = PAACAgent(cfg, PAACConfig(t_max=t_max, entropy_beta=0.01))
    pipe = PipelineConfig(actor_backend="process", num_actors=2,
                          queue_depth=2)
    with PipelinedRL(spec, agent, lr_schedule=constant(0.0007 * n_envs),
                     seed=seed, pipeline=pipe) as prl:
        res = prl.run(iters)
        platforms = prl._process_plane.worker_platforms()
        _assert_on(device, "process-backend learner params", prl.params)
    _assert_finite(res, "process backend")
    if platforms != ["cpu", "cpu"]:
        raise AssertionError(f"process backend: workers report platforms "
                             f"{platforms}, expected the host CPU")
    say(f"process backend: 2 workers on {platforms}, learner on "
        f"{device.platform}, {iters} updates")


def phase_kernels(tiny: bool = False, seed: int = 0) -> None:
    """Phase 5: each ``ops`` entry against its ``ref.py`` twin; the twins
    run at full f32 matmul precision so they are the oracle, not a second
    approximation."""
    import jax
    import numpy as np
    from repro.kernels.cases import kernel_cases

    for case in kernel_cases(tiny=tiny):
        args = case.make(jax.random.PRNGKey(seed))
        t0 = time.perf_counter()
        out = jax.block_until_ready(
            case.op(*args, **case.kwargs, backend="pallas"))
        secs = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            ref = case.op(*args, **case.kwargs, backend="ref")
        worst = 0.0
        for o, r in zip(_leaves(out), _leaves(ref)):
            o = np.asarray(o, np.float32)
            r = np.asarray(r, np.float32)
            np.testing.assert_allclose(o, r, rtol=case.tol, atol=case.tol,
                                       err_msg=case.name)
            worst = max(worst, float(np.max(np.abs(o - r))))
        say(f"kernel {case.name} ({case.source}): matches ref.py within "
            f"{case.tol:g}, max |diff| {worst:.3g}; first call incl. "
            f"compile {secs:.1f} s")


def _mesh_rollout(env, agent, n_envs: int, seed: int):
    """One rollout of the paper's job at ``n_envs`` envs, on one device."""
    import jax
    from repro.core.rollout import make_collect_fn
    from repro.models import init_policy

    k_init, k_env, k_act = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = init_policy(k_init, agent.cfg)
    state = env.reset(k_env)
    collect = jax.jit(make_collect_fn(agent.act_fn(), env, agent.hp.t_max))
    _, last_obs, _, traj = collect(params, state, env.observe(state), k_act)
    return params, traj, last_obs


def phase_mesh(devices, envs_per_lane: int = 32, t_max: int = 5,
               iters: int = 3, seed: int = 0,
               rtol: float = MESH_RTOL, atol: float = MESH_ATOL) -> None:
    """The mesh plane over ``devices``, and its sharded step against the
    one-device step."""
    import jax
    import numpy as np
    from repro.configs import PipelineConfig
    from repro.core.rollout import Transition
    from repro.distributed.sharding import (
        batch_sharding, replicated_sharding, traj_sharding,
    )
    from repro.launch.mesh import make_rollout_mesh
    from repro.optim import make_optimizer
    from repro.pipeline import PipelinedRL
    from repro.pipeline.learner import (
        make_learner_step, make_sharded_learner_step,
    )

    n = len(devices)
    lanes = [paper_setup(envs_per_lane, t_max) for _ in range(n)]
    agent, lr = lanes[0][1], lanes[0][2]
    pipe = PipelineConfig(queue_depth=1, lockstep=True, rollout_plane="mesh",
                          mesh_shape=n, num_actors=n)
    with PipelinedRL([env for env, _, _ in lanes], agent, lr_schedule=lr,
                     seed=seed, pipeline=pipe) as prl:
        res = prl.run(iters)
        lane_devices = prl._mesh_devices
        if len(set(lane_devices)) != n:
            raise AssertionError(f"mesh lanes share devices: {lane_devices}")
        for i, (dev, actor) in enumerate(zip(lane_devices, prl.actors)):
            _assert_on(dev, f"lane {i} obs/env state/key",
                       prl._actor_obs[i], prl._actor_env_state[i], actor.key)
        for leaf in _leaves(prl.params):
            if (leaf.sharding.device_set != set(devices)
                    or not leaf.sharding.is_fully_replicated):
                raise AssertionError("learner params are not replicated over "
                                     f"all {n} devices: {leaf.sharding}")
    _assert_finite(res, "mesh plane")
    say(f"mesh plane: {n} lanes on distinct devices, {envs_per_lane} envs "
        f"each, params replicated, {iters} lockstep updates, staleness "
        f"{res.mean_metrics['staleness']:.0f}")

    # one update on the 4-way-split batch vs the same update on one device
    env, _, _ = paper_setup(envs_per_lane * n, t_max)
    params, traj, last_obs = _mesh_rollout(env, agent, envs_per_lane * n,
                                           seed)
    opt = make_optimizer("rmsprop")
    opt_state = opt.init(params)
    step = jax.numpy.asarray(0, jax.numpy.int32)
    flat = jax.jit(make_learner_step(agent, opt, lr))
    p_one, _, m_one = flat(params, opt_state, traj, last_obs, step)
    mesh = make_rollout_mesh(n)
    repl = replicated_sharding(mesh)
    sharded = make_sharded_learner_step(agent, opt, lr, mesh,
                                        fused_publish=False)
    p_mesh, _, m_mesh = sharded(
        jax.device_put(params, repl), jax.device_put(opt_state, repl),
        Transition(*(jax.device_put(l, traj_sharding(mesh, l.ndim))
                     for l in traj)),
        jax.device_put(last_obs, batch_sharding(mesh, last_obs.ndim)), step,
    )
    for k in ("loss", "policy_loss", "value_loss", "entropy"):
        np.testing.assert_allclose(float(m_mesh[k]), float(m_one[k]),
                                   rtol=rtol, atol=atol, err_msg=k)
    worst = 0.0
    for a, b in zip(_leaves(p_one), _leaves(p_mesh)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(b, a, rtol=rtol, atol=atol)
        worst = max(worst, float(np.max(np.abs(a - b))))
    say(f"sharded learner step on {n} devices matches the one-device step: "
        f"max |param diff| {worst:.3g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh plane and its comparison")
    args = ap.parse_args(argv)
    try:
        from repro.utils import use_compile_cache

        say(f"compile cache: {use_compile_cache()}")
        devices = check_device(args.chips)
        device = devices[0]
        t0 = time.perf_counter()
        if args.chips == 4:
            phase_mesh(devices[:4])
        else:
            metrics = phase_sync(device)
            phase_pipelined(device, metrics)
            phase_process(device)
            phase_kernels()
        say(f"all phases passed in {time.perf_counter() - t0:.0f} s")
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
