"""The multi-process actor plane: shm contracts, equivalence, lifecycle.

Pins the third execution backend (``PipelineConfig.actor_backend =
"process"``):

* the shared-memory primitives honour their thread-plane twins' contracts
  — ``ShmStagingSet`` is writable/readable across attach boundaries with
  the ``StagingSet`` field layout, ``ShmParamSlot`` speaks
  ``PingPongParamSlot``'s reserve/commit protocol with cross-process
  reader leases,
* **equivalence** (the acceptance pin): a seeded single-actor lockstep
  process run learns from the identical rollout stream as the thread host
  plane — final params *bitwise* equal, metrics equal, RNG key synced
  back equal,
* multi-worker runs never drop a rollout (every ``(actor_id, seq)``
  learned exactly once) and zero-quota workers check out cleanly,
* a crashing env inside a worker subprocess surfaces as the actor error
  in ``run()`` without deadlock (EOF/crash propagation),
* config validation: live pools can't ride the process backend, the
  device rollout plane can't either.

Every env recipe here comes from ``repro.envs.pyemu`` (module-level
constructors): spawn ships specs by pickle *reference*, so closures would
die in the child — which is itself pinned in ``test_host_env.py``.
"""
import time

import jax
import numpy as np
import pytest

from repro.configs import PipelineConfig, get_config
from repro.core.agents import PAACAgent, PAACConfig
from repro.envs import HostEnvPool, py_bound_spec
from repro.pipeline import PipelinedRL, ShmParamSlot, ShmParamView, ShmStagingSet


def _vector_agent(obs_dim=4, t_max=3):
    cfg = get_config("paac_vector").replace(obs_shape=(obs_dim,),
                                            num_actions=3)
    return PAACAgent(cfg, PAACConfig(t_max=t_max))


def _pipe(**kw):
    base = dict(queue_depth=2, actor_backend="process")
    base.update(kw)
    return PipelineConfig(**base)


# ---------------------------------------------------------------------------
# shm staging set — StagingSet's layout across an attach boundary
# ---------------------------------------------------------------------------


def test_shm_staging_set_roundtrips_across_attach():
    parent = ShmStagingSet(t_max=2, n_envs=3, obs_shape=(4,),
                           obs_dtype=np.float32)
    try:
        assert parent.traj.obs.shape == (2, 3, 4)
        assert parent.traj.action.dtype == np.int32
        assert parent.last_obs.shape == (3, 4)
        child = ShmStagingSet(t_max=2, n_envs=3, obs_shape=(4,),
                              obs_dtype=np.float32, name=parent.name,
                              create=False)
        # writes through one mapping are visible through the other — the
        # zero-copy contract the drainer's Rollout wrapping relies on
        child.traj.obs[1, 2] = 7.0
        child.traj.reward[0] = [1.0, 2.0, 3.0]
        child.last_obs[:] = 5.0
        np.testing.assert_array_equal(parent.traj.obs[1, 2], np.full(4, 7.0))
        np.testing.assert_array_equal(parent.traj.reward[0], [1.0, 2.0, 3.0])
        np.testing.assert_array_equal(parent.last_obs, np.full((3, 4), 5.0))
        child.close()
    finally:
        parent.close()
        parent.unlink()


def test_shm_staging_set_attach_requires_name():
    with pytest.raises(ValueError):
        ShmStagingSet(1, 1, (), np.float32, create=False)


# ---------------------------------------------------------------------------
# shm param slot — PingPongParamSlot's reserve/commit, cross-process leases
# ---------------------------------------------------------------------------


def test_shm_param_slot_reserve_commit_and_leases():
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    tree = {"w": np.arange(4, dtype=np.float32), "b": np.zeros(2, np.float32)}
    slot = ShmParamSlot(tree, ctx, version=0)
    try:
        view = ShmParamView(slot.handle())
        params, v = view.read_params()
        assert v == 0
        np.testing.assert_array_equal(np.asarray(params["w"]),
                                      np.arange(4, dtype=np.float32))
        # a held lease on buffer v%2 blocks reserve(v+2) but not reserve(v+1)
        _, v0 = view.acquire()
        assert not slot.reserve(2, timeout=0.1)  # buffer 0 leased
        assert slot.reserve(1, timeout=0.1)      # buffer 1 free
        with pytest.raises(RuntimeError, match="reserve timed out"):
            slot.publish({"w": np.ones(4, np.float32),
                          "b": np.ones(2, np.float32)}, 2, timeout=0.1)
        view.release(v0)
        slot.publish({"w": np.full(4, 9.0, np.float32),
                      "b": np.ones(2, np.float32)}, 2, timeout=1.0)
        assert view.wait_for(2, timeout=1.0)
        params, v = view.read_params()
        assert v == 2
        np.testing.assert_array_equal(np.asarray(params["w"]),
                                      np.full(4, 9.0, np.float32))
        assert not view.wait_for(3, timeout=0.05)
        view.close()
    finally:
        slot.close()
        slot.unlink()


# ---------------------------------------------------------------------------
# end-to-end: the process backend through PipelinedRL.run
# ---------------------------------------------------------------------------


def test_process_backend_end_to_end_never_drops():
    spec = py_bound_spec(4, obs_dim=4, spin=0, n_workers=2)
    agent = _vector_agent()
    with PipelinedRL(spec, agent, lr_schedule=None, seed=0,
                     pipeline=_pipe()) as prl:
        res = prl.run(5)
        assert res.steps == 5 * 4 * 3
        assert np.isfinite(res.mean_metrics["loss"])
        assert sorted(prl.learned_ids) == [(0, s) for s in range(5)]
        # workers persist across runs: a second run reuses them
        res2 = prl.run(3)
        assert res2.steps == 8 * 4 * 3
        assert sorted(prl.learned_ids) == [(0, s) for s in range(3)]


def test_process_backend_multi_worker_spec_shard():
    """A single spec is sharded across workers (each child builds its own
    slice-pool); every (actor_id, seq) is learned exactly once."""
    spec = py_bound_spec(8, obs_dim=4, spin=0, n_workers=4)
    agent = _vector_agent()
    with PipelinedRL(spec, agent, lr_schedule=None, seed=0,
                     pipeline=_pipe(num_actors=2)) as prl:
        res = prl.run(6)
    assert res.steps == 6 * 4 * 3  # 4-env shards, not 8
    assert sorted(prl.learned_ids) == [(a, s) for a in range(2)
                                       for s in range(3)]
    assert len(res.per_actor_idle_s) == 2


def test_process_backend_zero_quota_workers_check_out():
    """iterations < num_actors: quota-0 workers must producer_done cleanly
    (no hang) and the stream still delivers every tagged rollout."""
    specs = [py_bound_spec(2, obs_dim=3, spin=0, n_workers=2,
                           base_seed=10 * a) for a in range(3)]
    agent = _vector_agent(obs_dim=3, t_max=2)
    with PipelinedRL(specs, agent, lr_schedule=None, seed=0,
                     pipeline=_pipe(num_actors=3)) as prl:
        t0 = time.perf_counter()
        res = prl.run(2)  # quota [1, 1, 0]
        assert time.perf_counter() - t0 < 120.0
    assert res.steps == 2 * 2 * 2
    assert sorted(prl.learned_ids) == [(0, 0), (1, 0)]


def test_worker_child_pins_its_jax_to_the_cpu(monkeypatch):
    """One process per chip: the parent learner holds the accelerator, so
    every worker pins its JAX to the CPU before any array exists. The
    children inherit a JAX_PLATFORMS naming no real platform: without the
    pin they could not act at all."""
    monkeypatch.setenv("JAX_PLATFORMS", "no-such-platform")
    spec = py_bound_spec(4, obs_dim=4, spin=0, n_workers=2)
    with PipelinedRL(spec, _vector_agent(), lr_schedule=None, seed=0,
                     pipeline=_pipe(num_actors=2)) as prl:
        assert prl._process_plane.worker_platforms() == [None, None]
        res = prl.run(2)
        assert prl._process_plane.worker_platforms() == ["cpu", "cpu"]
    assert np.isfinite(res.mean_metrics["loss"])


# ---------------------------------------------------------------------------
# equivalence pin (acceptance): process lockstep == thread lockstep, bitwise
# ---------------------------------------------------------------------------


def test_process_lockstep_bitwise_matches_thread_host_plane():
    """Seeded single-actor lockstep with infinite clips: the worker
    subprocess collects the *identical* rollout stream the thread host
    plane would (same key evolution, same params round-tripped through
    shm), so learning matches bitwise — params, metrics, and the synced
    RNG key."""
    def run_backend(backend):
        spec = py_bound_spec(4, obs_dim=4, spin=0, n_workers=2)
        agent = _vector_agent()
        inf = float("inf")
        with PipelinedRL(
            spec, agent, lr_schedule=None, seed=1,
            pipeline=_pipe(queue_depth=1, rho_bar=inf, c_bar=inf,
                           lockstep=True, actor_backend=backend),
        ) as prl:
            res = prl.run(6)
            params = jax.tree_util.tree_map(np.asarray, prl.params)
            return res, params, np.asarray(prl.key)

    r_t, p_t, k_t = run_backend("thread")
    r_p, p_p, k_p = run_backend("process")
    assert r_p.mean_metrics["staleness"] == 0.0
    for k in ("loss", "policy_loss", "value_loss", "entropy", "reward_sum"):
        assert r_p.mean_metrics[k] == r_t.mean_metrics[k], k
    for a, b in zip(jax.tree_util.tree_leaves(p_t),
                    jax.tree_util.tree_leaves(p_p)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(k_t, k_p)


# ---------------------------------------------------------------------------
# lifecycle: crash propagation, teardown, validation
# ---------------------------------------------------------------------------


def test_worker_env_crash_propagates_without_deadlock():
    from repro.envs.host_env import HostEnvSpec
    from repro.envs.pyemu import make_py_bound_env

    # obs_dim < 0 makes np.full raise inside the worker's first reset/step
    spec = HostEnvSpec(env_fn=make_py_bound_env, env_args=((0, -1, 0),),
                       n_workers=1, obs_shape=(1,))
    agent = _vector_agent(obs_dim=1, t_max=2)
    prl = PipelinedRL(spec, agent, lr_schedule=None, seed=0,
                      pipeline=_pipe())
    try:
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="actor 0 failed"):
            prl.run(4)
        assert time.perf_counter() - t0 < 120.0  # unwound, not deadlocked
    finally:
        prl.close()


def test_process_backend_rejects_live_pools_and_device_plane():
    agent = _vector_agent(obs_dim=1, t_max=2)
    with HostEnvPool([lambda s=0: None], n_workers=1,
                     obs_shape=(1,)) as pool:
        with pytest.raises(ValueError, match="HostEnvSpec"):
            PipelinedRL(pool, agent, pipeline=_pipe())
    spec = py_bound_spec(2, obs_dim=1, spin=0, n_workers=1)
    with pytest.raises(ValueError, match="host"):
        PipelinedRL(spec, agent,
                    pipeline=_pipe(rollout_plane="device"))
    with pytest.raises(ValueError, match="actor_backend"):
        PipelinedRL(spec, agent,
                    pipeline=PipelineConfig(actor_backend="fork"))


def test_close_is_idempotent_and_reaps_workers():
    spec = py_bound_spec(2, obs_dim=2, spin=0, n_workers=1)
    agent = _vector_agent(obs_dim=2, t_max=2)
    prl = PipelinedRL(spec, agent, lr_schedule=None, seed=0, pipeline=_pipe())
    procs = [w.proc for w in prl._process_plane._workers]
    prl.run(2)
    prl.close()
    prl.close()  # idempotent
    assert all(not p.is_alive() for p in procs)
