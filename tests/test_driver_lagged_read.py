"""The synchronous driver reads each update's metrics one update late.

Pins:

* ``run(n)`` reports the same ``mean_metrics``, ``episodes`` and
  ``host_reads`` (``==``) as an eager fold of the same updates — each
  update folded right after its dispatch — for GridWorld, a small
  ``FrameStack(AtariLike)`` and the ``agent_state`` agents (DQN,
  LaggedPAAC),
* on the fused path update k is folded only after update k+1 is
  dispatched (the last one after the loop), each exactly once and in
  order, with never more than two updates dispatched and not folded,
* the ``HostEnvPool`` path folds update k before update k+1's collect,
  whose staging buffers the update reads,
* ``RunResult.reads_waited`` counts the deferred folds that found their
  update still running (at most n) and is 0 on the ``HostEnvPool`` path;
  the driver's telemetry hub carries the same count.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import ParallelRL
from repro.core.agents import (
    DQNAgent,
    DQNConfig,
    LaggedConfig,
    LaggedPAACAgent,
    PAACAgent,
    PAACConfig,
)
from repro.core.framework import READS_WAITED, MetricsAccumulator
from repro.envs import AtariLike, FrameStack, GridWorld, py_bound_spec
from repro.optim import constant

STATE = ("params", "opt_state", "agent_state", "env_state", "obs", "key",
         "total_steps")


def _cfg(env, arch="paac_vector"):
    return get_config(arch).replace(obs_shape=env.obs_shape,
                                    num_actions=env.num_actions)


def _grid():
    env = GridWorld(8, size=4, max_steps=20)
    return ParallelRL(env, PAACAgent(_cfg(env), PAACConfig(t_max=3)),
                      lr_schedule=constant(1e-3), seed=0)


def _atari():
    env = FrameStack(AtariLike(2), n=4)
    return ParallelRL(env, PAACAgent(_cfg(env, "paac_nips"),
                                     PAACConfig(t_max=2)),
                      lr_schedule=constant(1e-3), seed=1)


def _dqn():
    env = GridWorld(8, size=3, max_steps=15)
    agent = DQNAgent(_cfg(env), DQNConfig(t_max=4, batch_size=16,
                                          eps_steps=50, target_sync=3))
    return ParallelRL(env, agent, optimizer="adam",
                      lr_schedule=constant(1e-3), seed=2,
                      replay_capacity=256)


def _lagged():
    env = GridWorld(8, size=3, max_steps=15)
    agent = LaggedPAACAgent(_cfg(env), LaggedConfig(t_max=4, delay=2),
                            mode="grad")
    return ParallelRL(env, agent, lr_schedule=constant(5e-3), seed=3)


BUILDERS = {"grid": _grid, "atari": _atari, "dqn": _dqn, "lagged": _lagged}


@pytest.fixture(scope="module")
def jobs():
    """One driver per job, built (and its step compiled) once per module;
    every test below restores the state it started from before comparing."""
    built = {}

    def get(name):
        if name not in built:
            built[name] = BUILDERS[name]()
        return built[name]

    return get


def _eager(rl, n):
    """The driver's loop with each update folded right after its dispatch
    (the fold this module compares the lagged one with)."""
    acc = MetricsAccumulator()
    step_arr = jnp.asarray(rl.total_steps, jnp.int32)
    for _ in range(n):
        acc.update(rl._dispatch(step_arr))
        step_arr = step_arr + 1
        rl.total_steps += rl._steps_per_iter
    return acc.result(rl.total_steps, rl._steps_per_iter)


def _lagged_and_eager(rl, n):
    """``rl.run(n)`` and the eager fold from the same state (the fused
    step donates nothing, so the arrays can be put back)."""
    start = {k: getattr(rl, k) for k in STATE}
    lagged = rl.run(n)
    end = {k: getattr(rl, k) for k in STATE}
    for k, v in start.items():
        setattr(rl, k, v)
    eager = _eager(rl, n)
    for a, b in zip(jax.tree_util.tree_leaves(end["params"]),
                    jax.tree_util.tree_leaves(rl.params)):
        assert bool(jnp.array_equal(a, b))
    assert end["total_steps"] == rl.total_steps
    return lagged, eager


@pytest.mark.parametrize("n", [1, 2, 7])
@pytest.mark.parametrize("job", ["grid", "atari"])
def test_lagged_fold_equals_the_eager_fold(jobs, job, n):
    lagged, eager = _lagged_and_eager(jobs(job), n)
    assert lagged.mean_metrics == eager.mean_metrics
    assert lagged.episodes == eager.episodes
    assert lagged.host_reads == eager.host_reads > 0
    assert lagged.steps == eager.steps


@pytest.mark.parametrize("job", ["dqn", "lagged"])
def test_agent_state_agents_fold_as_the_eager_loop(jobs, job):
    lagged, eager = _lagged_and_eager(jobs(job), 5)
    assert lagged.mean_metrics == eager.mean_metrics
    assert lagged.episodes == eager.episodes
    assert lagged.host_reads == eager.host_reads > 0


def _spy(monkeypatch, rl, events):
    """Record ("dispatch", k) and ("fold", k) in the order they happen."""
    dispatch = rl._dispatch
    dispatched = []  # kept alive, so no two updates share an id()

    def spied_dispatch(*args, **kw):
        metrics = dispatch(*args, **kw)
        dispatched.append(metrics)
        events.append(("dispatch", len(dispatched) - 1))
        return metrics

    update = MetricsAccumulator.update

    def spied_update(acc, metrics):
        k = next(i for i, m in enumerate(dispatched) if m is metrics)
        events.append(("fold", k))
        return update(acc, metrics)

    monkeypatch.setattr(rl, "_dispatch", spied_dispatch)
    monkeypatch.setattr(MetricsAccumulator, "update", spied_update)


def test_update_k_is_folded_after_update_k1_is_dispatched(jobs, monkeypatch):
    rl = jobs("grid")
    events = []
    _spy(monkeypatch, rl, events)
    n = 6
    rl.run(n)
    expected = [("dispatch", 0)]
    for k in range(1, n):
        expected += [("dispatch", k), ("fold", k - 1)]
    assert events == expected + [("fold", n - 1)]
    pending = 0
    for kind, _ in events:
        pending += 1 if kind == "dispatch" else -1
        assert 0 <= pending <= 2


def _host_rl():
    pool = py_bound_spec(4, obs_dim=4, spin=0, n_workers=2).build()
    cfg = get_config("paac_vector").replace(obs_shape=(4,), num_actions=3)
    return pool, ParallelRL(pool, PAACAgent(cfg, PAACConfig(t_max=2)),
                            lr_schedule=constant(1e-3), seed=0)


def test_host_env_path_folds_before_the_next_collect(monkeypatch):
    pool, rl = _host_rl()
    try:
        events = []
        _spy(monkeypatch, rl, events)
        collect = rl._host_collect

        def spied_collect():
            events.append(("collect", None))
            return collect()

        monkeypatch.setattr(rl, "_host_collect", spied_collect)
        res = rl.run(3)
    finally:
        pool.close()
    assert events == [e for k in range(3) for e in (
        ("collect", None), ("dispatch", k), ("fold", k))]
    assert res.reads_waited == 0


@pytest.mark.parametrize("ready", [True, False])
def test_reads_waited_counts_folds_that_found_the_update_running(
        jobs, monkeypatch, ready):
    rl = jobs("grid")
    monkeypatch.setattr(MetricsAccumulator, "_ready",
                        staticmethod(lambda metrics: ready))
    n = 5
    res = rl.run(n)
    assert res.reads_waited == (0 if ready else n)
    assert rl.telemetry.counter(READS_WAITED) == res.reads_waited


def test_reads_waited_is_at_most_n_on_the_fused_path(jobs):
    n = 8
    res = jobs("grid").run(n)
    assert 0 <= res.reads_waited <= n


def test_reads_waited_is_zero_on_the_host_env_path(monkeypatch):
    # even a fold that would wait is not counted: nothing is deferred there
    monkeypatch.setattr(MetricsAccumulator, "_ready",
                        staticmethod(lambda metrics: False))
    pool, rl = _host_rl()
    try:
        res = rl.run(4)
    finally:
        pool.close()
    assert res.reads_waited == 0
    assert rl.telemetry.counter(READS_WAITED) == 0


def test_logging_reads_only_folded_updates(jobs, monkeypatch):
    from jax._src.array import ArrayImpl

    rl = jobs("grid")
    events = []
    _spy(monkeypatch, rl, events)
    floats = []
    to_float = ArrayImpl.__float__

    def counted(self):
        floats.append(self)
        return to_float(self)

    monkeypatch.setattr(ArrayImpl, "__float__", counted)
    n = 4
    res = rl.run(n, log_every=1)
    # logging every iteration adds no fold and no read of the update in
    # flight: every device scalar converted is one the folds counted
    assert [k for kind, k in events if kind == "fold"] == list(range(n))
    assert len(floats) == res.host_reads == 6 * n


def test_host_copy_starts_at_dispatch_for_every_device_scalar(
        jobs, monkeypatch):
    from jax._src.array import ArrayImpl

    rl = jobs("grid")
    events = []
    _spy(monkeypatch, rl, events)
    start_copy = ArrayImpl.copy_to_host_async

    def spied_copy(self):
        events.append(("copy", None))
        return start_copy(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spied_copy)
    n = 3
    res = rl.run(n)
    # each dispatch is followed by its six copies, before any fold
    per_update = [("copy", None)] * (res.host_reads // n)
    expected = [("dispatch", 0)] + per_update
    for k in range(1, n):
        expected += [("dispatch", k)] + per_update + [("fold", k - 1)]
    assert events == expected + [("fold", n - 1)]
