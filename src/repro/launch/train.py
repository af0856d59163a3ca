"""Training launcher.

Two modes:

* ``--mode rl`` (default): full PAAC RL (Algorithm 1) against a JAX token
  environment — rollout with the current policy, synchronous update.
  Works at reduced scale on CPU; on a pod the same code runs the
  production mesh (actions/envs sharded over the data axes).
* ``--mode synthetic``: the sharded trajectory train step on synthetic
  batches — the profiling configuration matching the dry-run's train_4k.

``--pipeline`` swaps the synchronous ``ParallelRL`` backend for the
asynchronous actor/learner pipeline (``repro.pipeline.PipelinedRL``):
``--num-actors`` replicas (the env axis split between them) collect
rollouts while the learner consumes earlier ones, with ``--queue-depth``
bounding staleness and ``--rho-bar``/``--c-bar`` the V-trace clips on the
off-policy importance correction. ``--rollout-plane`` picks the trajectory
queue plane: the device-resident ring (JAX-native envs, donated buffers —
the fast path) or the host staging queue (external env pools; also the
GA3C-style baseline for benchmarking JAX envs). ``--actor-backend process``
moves each actor replica into a worker subprocess (shared-memory rollouts
and param broadcast) — the only backend that scales GIL-holding Python
emulators; it drives the ``--host-env`` Python-bound emulator pool with
``--env-spin`` pure-Python work per step. ``--mesh D`` scales the device
plane across ``D`` accelerators: one actor lane per device feeds a
per-device sub-ring, the learner consumes a globally-sharded batch and
all-reduces its gradients over the mesh's data axis (on CPU, expose fake
devices first: ``XLA_FLAGS=--xla_force_host_platform_device_count=D``).
``--trace``/``--metrics-jsonl``/``--stall-timeout`` turn on the pipeline's
observability exports (``repro.telemetry``; see docs/observability.md): a
Perfetto-viewable Chrome trace of every plane's spans, a JSONL liveness
heartbeat, and the stall watchdog naming the stage each party is blocked
in when progress stops.

``--elastic`` arms the pipeline's actor supervisor: crashed replicas
respawn under ``--restart-budget`` (exponential ``--restart-backoff``),
then the run degrades to fewer actors with the dead replica's quota
reassigned — instead of the fail-fast default. ``--checkpoint-dir`` +
``--checkpoint-every N`` snapshot the full pipeline state every N updates;
``--resume`` restores the newest snapshot and runs only the remainder
(bitwise-equal to the uninterrupted run on the thread backend's FIFO
planes). ``--fault-kill``/``--fault-stall-learner`` drive the
deterministic fault-injection harness (``repro.pipeline.faults``) for
chaos testing. See docs/fault_tolerance.md.

``--replay`` swaps the pipeline's FIFO trajectory ring for the sampled
``ReplayRing`` (the off-policy plane): actors never block — a full ring
evicts its oldest rollout — and each learner update samples
``--replay-batch`` of the ``--replay-capacity`` resident rollouts
(uniformly, or TD-error-weighted with ``--prioritized``). ``--algo dqn``
selects the value-based agent: synchronous scan-based DQN without
``--pipeline``, the replay-fed pipelined TD learner with
``--pipeline --replay``; ``--algo paac`` (default) under ``--replay``
runs the V-trace learner off-policy on sampled stale rollouts.

Examples:
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --iterations 20
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
        --iterations 20 --pipeline --queue-depth 2 --rho-bar 1.0
    PYTHONPATH=src python -m repro.launch.train --arch paac_vector \
        --iterations 40 --pipeline --num-actors 4 --n-envs 16
    PYTHONPATH=src python -m repro.launch.train --arch paac_vector \
        --algo dqn --iterations 40 --pipeline --replay --num-actors 2 \
        --replay-capacity 32 --replay-batch 1
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --reduced \
        --mode synthetic --iterations 5
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core import ParallelRL
from repro.core.agents import PAACAgent, PAACConfig
from repro.envs import TokenEnv
from repro.launch.steps import build_train_step
from repro.models import init_policy
from repro.optim import constant
from repro.utils import get_logger, use_compile_cache

log = get_logger("train")


def run_rl(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.actor_backend == "process" and not args.pipeline:
        raise SystemExit(
            "--actor-backend process is a pipeline backend: add --pipeline "
            "(the synchronous ParallelRL driver has no actor replicas)"
        )
    if args.mesh > 1 and not args.pipeline:
        raise SystemExit(
            "--mesh is a pipeline (mesh rollout plane) knob: add --pipeline"
        )
    if (args.trace or args.metrics_jsonl or args.stall_timeout) \
            and not args.pipeline:
        raise SystemExit(
            "--trace/--metrics-jsonl/--stall-timeout observe the pipeline "
            "backend's telemetry hub: add --pipeline"
        )
    if args.sanitize and not args.pipeline:
        raise SystemExit(
            "--sanitize arms the pipeline backend's runtime sanitizers "
            "(repro.analysis): add --pipeline"
        )
    if args.sanitize:
        from repro.analysis import enable_sanitizers

        try:
            modes = enable_sanitizers(args.sanitize)
        except ValueError as e:
            raise SystemExit(f"--sanitize: {e}")
        log.info("sanitizers armed: %s", ",".join(sorted(modes)))
    if args.replay and not args.pipeline:
        raise SystemExit(
            "--replay selects the pipeline's sampled ReplayRing plane: add "
            "--pipeline (the synchronous DQN has its own scan-based replay)"
        )
    if args.prioritized and not args.replay:
        raise SystemExit(
            "--prioritized weights the ReplayRing's sampling: add --replay"
        )
    if args.algo == "dqn" and args.pipeline and not args.replay:
        raise SystemExit(
            "--algo dqn under --pipeline needs the replay plane: add "
            "--replay (the FIFO planes feed the on-policy V-trace learner)"
        )
    if args.replay and (args.host_env or args.actor_backend == "process"):
        raise SystemExit(
            "--replay requires a JAX-native env on the device plane: it "
            "cannot combine with --host-env/--actor-backend process"
        )
    if (args.elastic or args.fault_kill or args.fault_stall_learner
            or args.checkpoint_every or args.resume) and not args.pipeline:
        raise SystemExit(
            "--elastic/--fault-*/--checkpoint-every/--resume drive the "
            "pipeline backend's fault-tolerance plane: add --pipeline"
        )
    if (args.checkpoint_every or args.resume) and not args.checkpoint_dir:
        raise SystemExit(
            "--checkpoint-every/--resume need --checkpoint-dir (where the "
            "pipeline's full-state snapshots live)"
        )
    host_env = args.host_env or args.actor_backend == "process"
    if host_env:
        # GIL-holding external-emulator path (repro.envs.pyemu): the regime
        # --actor-backend process exists for. Needs a policy that acts on
        # the raw vector observation.
        if cfg.family != "cnn":
            raise SystemExit(
                f"--host-env/--actor-backend process need a vector/cnn "
                f"policy (e.g. --arch paac_vector), got {args.arch}"
            )
        from repro.envs import py_bound_spec

        spec = py_bound_spec(args.n_envs, obs_dim=16, spin=args.env_spin,
                             n_workers=min(8, args.n_envs))
        cfg = cfg.replace(obs_shape=spec.obs_shape, num_actions=3)
        env = spec if args.pipeline else spec.build()
    else:
        env = TokenEnv(args.n_envs, vocab=min(cfg.vocab_size, 64),
                       ctx=args.ctx, k=2, horizon=64)
        cfg = cfg.replace(num_actions=env.vocab)
        if cfg.family == "cnn":  # vector/cnn policies act on the raw obs
            cfg = cfg.replace(obs_shape=env.obs_shape)
    if args.algo == "dqn":
        from repro.core.agents import DQNAgent, DQNConfig

        agent = DQNAgent(cfg, DQNConfig(t_max=args.t_max))
    else:
        agent = PAACAgent(cfg, PAACConfig(t_max=args.t_max,
                                          entropy_beta=0.01))
    if args.pipeline:
        from repro.configs import PipelineConfig
        from repro.pipeline import FaultPlan, PipelinedRL

        fault_plan = None
        if args.fault_kill or args.fault_stall_learner:
            kills = []
            for spec in args.fault_kill:
                parts = spec.split(":")
                if len(parts) not in (2, 3):
                    raise SystemExit(
                        f"--fault-kill {spec!r}: expected "
                        "slot:after_rollouts[:mode]"
                    )
                kills.append((int(parts[0]), int(parts[1]),
                              parts[2] if len(parts) == 3 else "error"))
            stalls = []
            for spec in args.fault_stall_learner:
                it, _, sec = spec.partition(":")
                if not sec:
                    raise SystemExit(
                        f"--fault-stall-learner {spec!r}: expected "
                        "iteration:seconds"
                    )
                stalls.append((int(it), float(sec)))
            fault_plan = FaultPlan(kills=tuple(kills),
                                   stall_learner=tuple(stalls))
        rl = PipelinedRL(
            env, agent, lr_schedule=constant(args.lr), seed=args.seed,
            pipeline=PipelineConfig(queue_depth=args.queue_depth,
                                    rho_bar=args.rho_bar, c_bar=args.c_bar,
                                    num_actors=args.num_actors,
                                    rollout_plane=args.rollout_plane,
                                    actor_backend=args.actor_backend,
                                    mesh_shape=args.mesh,
                                    replay_plane=args.replay,
                                    replay_capacity=args.replay_capacity,
                                    replay_batch=args.replay_batch,
                                    prioritized=args.prioritized,
                                    trace_path=args.trace,
                                    metrics_jsonl=args.metrics_jsonl,
                                    stall_timeout_s=args.stall_timeout,
                                    elastic=args.elastic,
                                    restart_budget=args.restart_budget,
                                    restart_backoff_s=args.restart_backoff,
                                    lease_timeout_s=args.lease_timeout,
                                    fault_plan=fault_plan,
                                    checkpoint_dir=args.checkpoint_dir,
                                    checkpoint_every=args.checkpoint_every),
        )
    else:
        rl = ParallelRL(env, agent, lr_schedule=constant(args.lr),
                        seed=args.seed)
    resume_done = 0
    if args.pipeline and args.resume:
        resume_done = rl.restore()
        if resume_done:
            log.info("resume: checkpoint covers %d update(s) — running the "
                     "remainder", resume_done)
    try:
        for epoch in range(args.epochs):
            iters = args.iterations
            if epoch == 0 and resume_done:
                iters = max(args.iterations - resume_done, 0)
                if iters == 0:
                    log.info("resume: epoch 0 fully covered by checkpoint")
                    continue
            res = rl.run(iters,
                         log_every=max(args.iterations // 4, 1))
            log.info(
                "epoch %d steps=%d mean_reward/iter=%.3f tps=%.0f%s",
                epoch, res.steps, res.mean_metrics.get("reward_sum", 0.0),
                res.timesteps_per_sec,
                (f" staleness={res.mean_metrics.get('staleness', 0.0):.1f}"
                 f" actor_idle={res.actor_idle_s:.2f}s"
                 f" learner_idle={res.learner_idle_s:.2f}s"
                 if args.pipeline else ""),
            )
        if args.checkpoint:
            save_checkpoint(args.checkpoint, rl.total_steps, rl.params)
            log.info("checkpoint saved to %s", args.checkpoint)
        if args.sanitize and "locks" in args.sanitize:
            # the run's lock-order verdict (also embedded in --trace output):
            # a cycle or wait-while-holding hazard is a latent deadlock —
            # fail the launch so CI catches it
            from repro.analysis.lockcheck import monitor

            rep = monitor().report()
            if rep["cycles"] or rep["hazards"]:
                for cyc in rep["cycles"]:
                    log.error("lockcheck: lock-order cycle %s",
                              " -> ".join(cyc))
                for h in rep["hazards"]:
                    log.error(
                        "lockcheck: %s waited on %s while holding %s",
                        h["thread"], h["waiting_on"], ", ".join(h["holding"]))
                raise SystemExit(
                    f"lockcheck: {len(rep['cycles'])} cycle(s), "
                    f"{len(rep['hazards'])} hazard(s) — see log"
                )
            log.info("lockcheck: %d lock-order edge(s), no cycles, "
                     "no hazards", len(rep["edges"]))
    finally:
        if hasattr(rl, "close"):
            rl.close()  # worker subprocesses / spec-built pools
        elif host_env and not args.pipeline:
            env.close()
    return rl


def run_synthetic(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    B, T = args.n_envs, args.t_max
    key = jax.random.PRNGKey(args.seed)
    params = init_policy(key, cfg)
    step_fn, opt = build_train_step(cfg, n_e=B)
    opt_state = opt.init(params)
    step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
    batch = {
        "tokens": jax.random.randint(key, (B, T + 1), 0, cfg.vocab_size),
        "rewards": jax.random.uniform(key, (B, T)),
        "dones": jnp.zeros((B, T), bool),
    }
    t0 = time.perf_counter()
    for i in range(args.iterations):
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             jnp.asarray(i, jnp.int32))
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    log.info(
        "synthetic: %d iters, %.1f tokens/s, loss=%.4f",
        args.iterations, args.iterations * B * T / dt, float(metrics["loss"]),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ASSIGNED_ARCHS + ["paac_vector"],
                    default="mamba2-370m")
    ap.add_argument("--mode", choices=("rl", "synthetic"), default="rl")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--iterations", type=int, default=50)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--n-envs", type=int, default=16)
    ap.add_argument("--t-max", type=int, default=8)
    ap.add_argument("--ctx", type=int, default=32)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--pipeline", action="store_true",
                    help="use the asynchronous actor/learner pipeline backend")
    ap.add_argument("--queue-depth", type=int, default=2,
                    help="trajectory queue depth (max rollouts in flight)")
    ap.add_argument("--rho-bar", type=float, default=1.0,
                    help="importance-weight clip for stale rollouts (V-trace ρ̄)")
    ap.add_argument("--c-bar", type=float, default=1.0,
                    help="V-trace c̄: clip on the backward-propagation product")
    ap.add_argument("--num-actors", type=int, default=1,
                    help="actor replicas feeding the learner (env axis split)")
    ap.add_argument("--rollout-plane",
                    choices=("auto", "device", "host", "mesh"),
                    default="auto",
                    help="trajectory queue plane: device-resident ring "
                    "(JAX envs), host staging queue, mesh sub-rings "
                    "(multi-device), or auto by env type / --mesh")
    ap.add_argument("--mesh", type=int, default=1,
                    help="mesh rollout plane over this many devices: one "
                    "actor lane per device, env axis sharded, gradients "
                    "all-reduced over the mesh's data axis (CPU: set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--algo", choices=("paac", "dqn"), default="paac",
                    help="agent family: on-policy PAAC (V-trace under the "
                    "pipeline) or value-based DQN (scan-based sync, or the "
                    "replay-fed pipelined learner with --pipeline --replay)")
    ap.add_argument("--replay", action="store_true",
                    help="pipeline: swap the FIFO trajectory ring for the "
                    "sampled ReplayRing (off-policy plane; actors never "
                    "block — a full ring evicts its oldest rollout)")
    ap.add_argument("--replay-capacity", type=int, default=64,
                    help="ReplayRing capacity in resident rollouts "
                    "(each n_envs/num_actors × t_max transitions)")
    ap.add_argument("--replay-batch", type=int, default=1,
                    help="rollouts sampled per learner update")
    ap.add_argument("--prioritized", action="store_true",
                    help="TD-error-weighted replay sampling (else uniform)")
    ap.add_argument("--actor-backend", choices=("thread", "process"),
                    default="thread",
                    help="where actor replicas run: threads (GIL-free env "
                    "stepping) or worker subprocesses (GIL-holding Python "
                    "emulators; implies the host-env path)")
    ap.add_argument("--host-env", action="store_true",
                    help="drive the Python-bound emulator pool "
                    "(repro.envs.pyemu) instead of the JAX TokenEnv")
    ap.add_argument("--env-spin", type=int, default=2000,
                    help="pure-Python work per host-env step (GIL-holding "
                    "emulator cost model)")
    ap.add_argument("--trace", default="",
                    help="write a Chrome trace-event JSON of the run's spans "
                    "here (open in Perfetto); pipeline backend only")
    ap.add_argument("--metrics-jsonl", default="",
                    help="append a JSONL metrics heartbeat (steps/s EMA, "
                    "queue depth, staleness, per-actor liveness) here")
    ap.add_argument("--sanitize", default="",
                    help="arm runtime sanitizers (comma-separated: 'locks' "
                    "for the lock-order deadlock detector — the launch "
                    "fails on cycles/wait-while-holding hazards — and "
                    "'transfers' for jax transfer guards + donated-buffer "
                    "probes on the device planes); same effect as the "
                    "REPRO_SANITIZE env var. Pipeline backend only.")
    ap.add_argument("--stall-timeout", type=float, default=0.0,
                    help="stall watchdog window in seconds: when the learner "
                    "or an actor makes no progress for this long, log which "
                    "stage each party is blocked in (0 = off)")
    ap.add_argument("--elastic", action="store_true",
                    help="supervise actor replicas: respawn crashed actors "
                    "under --restart-budget, then degrade to fewer actors "
                    "(default is fail-fast; mesh plane is always fail-fast)")
    ap.add_argument("--restart-budget", type=int, default=1,
                    help="respawns allowed per actor slot before the "
                    "supervisor degrades the run (0 = degrade immediately)")
    ap.add_argument("--restart-backoff", type=float, default=0.05,
                    help="base respawn backoff in seconds (doubles per "
                    "attempt on the same slot)")
    ap.add_argument("--lease-timeout", type=float, default=60.0,
                    help="learner-side param-lease timeout: error naming the "
                    "holding party when a lease is never released")
    ap.add_argument("--fault-kill", action="append", default=[],
                    metavar="SLOT:AFTER[:MODE]",
                    help="deterministic fault injection: kill actor slot "
                    "SLOT after AFTER produced rollouts; MODE is 'error' "
                    "(raise in-replica, default) or 'exit' (hard process "
                    "exit, process backend). Repeatable.")
    ap.add_argument("--fault-stall-learner", action="append", default=[],
                    metavar="ITER:SECONDS",
                    help="deterministic fault injection: sleep SECONDS in "
                    "the learner loop before update ITER. Repeatable.")
    ap.add_argument("--checkpoint-dir", default="",
                    help="directory for the pipeline's full-state "
                    "checkpoints (params, opt state, RNG keys, per-actor "
                    "seq counters, queue tickets)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save a pipeline checkpoint every N learner "
                    "updates (0 = off; requires --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint in --checkpoint-dir "
                    "and run only the remaining iterations (bitwise "
                    "continuation on the thread backend's FIFO planes)")
    args = ap.parse_args()
    use_compile_cache()
    if args.mode == "rl":
        run_rl(args)
    else:
        run_synthetic(args)


if __name__ == "__main__":
    main()
