"""The synchronous driver reads each dispatch's metrics one dispatch late.

On the fused path a dispatch runs a chunk of up to
``UPDATES_PER_DISPATCH`` updates and hands back their metrics packed in
one array, so these pins are per dispatch (chunk j), not per update:

* ``run(n)`` reports the same ``mean_metrics``, ``episodes`` and
  ``host_reads`` (``==``) as an eager fold of the same chunks — each
  chunk folded right after its dispatch — for GridWorld, a small
  ``FrameStack(AtariLike)`` and the ``agent_state`` agents (DQN,
  LaggedPAAC),
* on the fused path chunk j is folded only after chunk j+1 is dispatched
  (the last one after the loop), each exactly once and in order, with
  never more than two chunks dispatched and not folded,
* the ``HostEnvPool`` path (one update per dispatch) folds update k
  before update k+1's collect, whose staging buffers the update reads,
* ``RunResult.reads_waited`` counts the deferred folds that found their
  chunk still running (at most the number of dispatches) and is 0 on the
  ``HostEnvPool`` path; the driver's telemetry hub carries the same
  count,
* each dispatch's metrics reach the host in one transfer, started at
  dispatch and read inside the fold.
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
from repro.core.framework import (
    READS_WAITED,
    UPDATES_PER_DISPATCH,
    MetricsAccumulator,
)
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


def _dispatches(n):
    return -(-n // UPDATES_PER_DISPATCH)


def _eager(rl, n):
    """The driver's loop with each chunk folded right after its dispatch
    (the fold this module compares the lagged one with)."""
    acc = MetricsAccumulator()
    step_arr = jnp.asarray(rl.total_steps, jnp.int32)
    done = 0
    while done < n:
        k = min(UPDATES_PER_DISPATCH, n - done)
        step_arr, metrics = rl._dispatch(step_arr, k)
        acc.update(metrics)
        done += k
        rl.total_steps += k * rl._steps_per_iter
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


@pytest.mark.parametrize("n", [1, 2, 7, 17])
@pytest.mark.parametrize("job", ["grid", "atari"])
def test_lagged_fold_equals_the_eager_fold(jobs, job, n):
    lagged, eager = _lagged_and_eager(jobs(job), n)
    assert lagged.mean_metrics == eager.mean_metrics
    assert lagged.episodes == eager.episodes
    assert lagged.host_reads == eager.host_reads == _dispatches(n)
    assert lagged.steps == eager.steps


@pytest.mark.parametrize("job", ["dqn", "lagged"])
def test_agent_state_agents_fold_as_the_eager_loop(jobs, job):
    lagged, eager = _lagged_and_eager(jobs(job), 13)
    assert lagged.mean_metrics == eager.mean_metrics
    assert lagged.episodes == eager.episodes
    assert lagged.host_reads == eager.host_reads == 2


def _spy(monkeypatch, rl, events):
    """Record ("dispatch", j) and ("fold", j) in the order they happen."""
    dispatch = rl._dispatch
    dispatched = []  # kept alive, so no two dispatches share an id()

    def spied_dispatch(*args, **kw):
        step_arr, metrics = dispatch(*args, **kw)
        dispatched.append(metrics)
        events.append(("dispatch", len(dispatched) - 1))
        return step_arr, metrics

    update = MetricsAccumulator.update

    def spied_update(acc, metrics):
        j = next(i for i, m in enumerate(dispatched) if m is metrics)
        events.append(("fold", j))
        return update(acc, metrics)

    monkeypatch.setattr(rl, "_dispatch", spied_dispatch)
    monkeypatch.setattr(MetricsAccumulator, "update", spied_update)


def _spy_transfers(monkeypatch, events):
    """Record ("transfer", None) for each device array brought to the host
    (JAX's ``np.asarray(jax.Array)`` path)."""
    from jax._src.array import ArrayImpl

    value = ArrayImpl._value

    def counted(self):
        events.append(("transfer", None))
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(counted))


def test_update_k_is_folded_after_update_k1_is_dispatched(jobs, monkeypatch):
    # per dispatch: chunk j is folded after chunk j+1 is dispatched
    rl = jobs("grid")
    events = []
    _spy(monkeypatch, rl, events)
    n = 5 * UPDATES_PER_DISPATCH + 1
    res = rl.run(n)
    chunks = _dispatches(n)
    assert res.dispatches == chunks == 6
    expected = [("dispatch", 0)]
    for j in range(1, chunks):
        expected += [("dispatch", j), ("fold", j - 1)]
    assert events == expected + [("fold", chunks - 1)]
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
    # per dispatch: a deferred fold counts once for its whole chunk
    rl = jobs("grid")
    monkeypatch.setattr(MetricsAccumulator, "_ready",
                        staticmethod(lambda metrics: ready))
    n = 20
    res = rl.run(n)
    assert res.dispatches == _dispatches(n) == 3
    assert res.reads_waited == (0 if ready else res.dispatches)
    assert rl.telemetry.counter(READS_WAITED) == res.reads_waited


def test_reads_waited_is_at_most_n_on_the_fused_path(jobs):
    # at most the number of dispatches, which is under n
    n = 20
    res = jobs("grid").run(n)
    assert 0 <= res.reads_waited <= res.dispatches == _dispatches(n)


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
    rl = jobs("grid")
    events = []
    _spy(monkeypatch, rl, events)
    transfers = []
    _spy_transfers(monkeypatch, transfers)
    n = 20
    res = rl.run(n, log_every=1)
    # logging at every dispatch adds no fold and no read of the chunk in
    # flight: every transfer is one the folds counted, one per dispatch
    assert [j for kind, j in events if kind == "fold"] == [0, 1, 2]
    assert len(transfers) == res.host_reads == res.dispatches == 3


def test_host_copy_starts_at_dispatch_for_every_device_scalar(
        jobs, monkeypatch):
    # per dispatch: one copy of the packed metrics, started at dispatch,
    # and one transfer inside the fold that reads it
    from jax._src.array import ArrayImpl

    rl = jobs("grid")
    events = []
    _spy(monkeypatch, rl, events)
    start_copy = ArrayImpl.copy_to_host_async

    def spied_copy(self):
        events.append(("copy", None))
        return start_copy(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spied_copy)
    _spy_transfers(monkeypatch, events)
    n = 20
    res = rl.run(n)
    chunks = res.dispatches
    assert chunks == _dispatches(n) == res.host_reads
    # the read may ask for the copy again (jax.device_get does): a no-op
    # once it has started, so only the copies outside the folds count
    events = [e for i, e in enumerate(events)
              if not (e[0] == "copy" and events[i - 1][0] == "fold")]
    copy, fold = [("copy", None)], [("transfer", None)]
    expected = [("dispatch", 0)] + copy
    for j in range(1, chunks):
        expected += [("dispatch", j)] + copy + [("fold", j - 1)] + fold
    assert events == expected + [("fold", chunks - 1)] + fold
