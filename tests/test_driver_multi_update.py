"""The fused path runs up to ``UPDATES_PER_DISPATCH`` updates per dispatch.

Pins:

* ``run(n)`` equals n single-update dispatches (trip count 1) bit for
  bit: parameters, optimizer state, agent state, environment state,
  observations, key and ``total_steps``, with ``==`` on ``mean_metrics``,
  ``episodes`` and the ``count.*`` run totals handed to JAX's monitoring,
  for GridWorld, a small ``FrameStack(AtariLike)`` NIPS job, DQN and
  LaggedPAAC,
* ``RunResult.dispatches`` (and the hub counter ``dispatches``) is
  ceil(n / 8), with one device-to-host transfer per dispatch,
* ``compiled()`` is the program ``run`` dispatches: one executable serves
  every trip count, and its HLO has the update loop, every named scope,
  and the same instructions and op names as the executable the run used,
* a ``_train_step`` replaced before the first run reaches every update,
* ``log_every`` logs at the first dispatch boundary at or past each
  multiple.
"""
import logging
import re

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
    DISPATCHES,
    RUN_COUNTER_EVENT,
    UPDATES_PER_DISPATCH,
    MetricsAccumulator,
    publish_counters,
)
from repro.envs import AtariLike, FrameStack, GridWorld
from repro.optim import constant

STATE = ("params", "opt_state", "agent_state", "env_state", "obs", "key")
SCOPES = ("rollout", "policy.forward", "env.step", "learner", "loss_grad",
          "optimizer")
COUNT = "count.steps_seen"


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


def _counting(rl):
    """Replace the built driver's train step with one that also returns a
    ``count.*`` metric (the step counter it was given, plus one)."""
    step = rl._train_step

    def counted(*args):
        *out, metrics = step(*args)
        metrics = dict(metrics, **{COUNT: (args[-1] + 1).astype(jnp.float32)})
        return (*out, metrics)

    rl._train_step = counted
    return rl


BUILDERS = {"grid": lambda: _counting(_grid()), "atari": _atari,
            "dqn": _dqn, "lagged": _lagged}


@pytest.fixture(scope="module")
def jobs():
    """One driver per job, built (and its loop compiled) once per module;
    every test below restores the state it started from before comparing."""
    built = {}

    def get(name):
        if name not in built:
            built[name] = BUILDERS[name]()
        return built[name]

    return get


@pytest.fixture
def run_counters():
    """The ``count.*`` run totals published while the test runs."""
    seen = {}

    def listen(event, value, **_kw):
        if event.startswith(RUN_COUNTER_EVENT):
            seen.setdefault(event[len(RUN_COUNTER_EVENT):], []).append(value)

    jax.monitoring.register_scalar_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_scalar_listener(listen)


def _dispatches(n):
    return -(-n // UPDATES_PER_DISPATCH)


def _single_updates(rl, n):
    """n dispatches of one update each, each folded at once; its run
    totals published as ``run`` publishes them."""
    acc = MetricsAccumulator()
    step_arr = jnp.asarray(rl.total_steps, jnp.int32)
    for _ in range(n):
        step_arr, metrics = rl._dispatch(step_arr, 1)
        acc.update(metrics)
        rl.total_steps += rl._steps_per_iter
    publish_counters(acc.acc)
    return acc.result(rl.total_steps, rl._steps_per_iter)


def _leaves(rl):
    return jax.tree_util.tree_leaves([getattr(rl, k) for k in STATE])


@pytest.mark.parametrize("n", [1, 7, 8, 9, 17])
@pytest.mark.parametrize("job", ["grid", "atari", "dqn", "lagged"])
def test_run_equals_single_update_dispatches(jobs, run_counters, job, n):
    rl = jobs(job)
    start = {k: getattr(rl, k) for k in STATE + ("total_steps",)}
    looped = rl.run(n)
    end_leaves, end_steps = _leaves(rl), rl.total_steps
    for k, v in start.items():
        setattr(rl, k, v)
    single = _single_updates(rl, n)
    assert end_steps == rl.total_steps == start["total_steps"] + n * (
        rl._steps_per_iter)
    ends = _leaves(rl)
    assert len(end_leaves) == len(ends) > 0
    for a, b in zip(end_leaves, ends):
        assert a.dtype == b.dtype
        assert bool(jnp.array_equal(a, b))
    assert looped.mean_metrics == single.mean_metrics
    assert looped.episodes == single.episodes
    assert looped.steps == single.steps
    assert looped.dispatches == _dispatches(n)
    if job == "grid":
        assert COUNT in looped.mean_metrics
        assert run_counters[COUNT][0] == run_counters[COUNT][1] > 0


@pytest.mark.parametrize("n", [1, 8, 9, 17, 30])
def test_one_transfer_per_dispatch(jobs, monkeypatch, n):
    from jax._src.array import ArrayImpl

    rl = jobs("grid")
    transfers = []
    value = ArrayImpl._value

    def counted(self):
        transfers.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(counted))
    res = rl.run(n)
    assert res.dispatches == _dispatches(n)
    assert rl.telemetry.counter(DISPATCHES) == res.dispatches
    assert res.host_reads == len(transfers) == res.dispatches
    # each transfer is one dispatch's packed metrics
    width = len(rl._metric_keys)
    assert transfers == [(UPDATES_PER_DISPATCH, width)] * res.dispatches


def _instructions(hlo_text):
    """(instruction, op_name) of every instruction that carries one."""
    return re.findall(r'^\s*(?:ROOT\s+)?(%[^\s=]+) = .*op_name="([^"]*)"',
                      hlo_text, flags=re.M)


def test_compiled_is_the_program_run_dispatches(jobs, monkeypatch):
    rl = jobs("atari")
    rl.run(1)  # the loop is built and compiled
    loop = rl._loop
    calls = []

    def spied(*args):
        calls.append(args)
        return loop(*args)

    monkeypatch.setattr(rl, "_loop", spied)
    rl.run(UPDATES_PER_DISPATCH + 1)
    monkeypatch.undo()
    # trip counts 8 and 1 went to the one executable, as will compiled()
    assert [int(c[2]) for c in calls] == [UPDATES_PER_DISPATCH, 1]
    text = rl.compiled().as_text()
    assert loop._cache_size() == 1
    used = loop.lower(*calls[0]).compile().as_text()
    assert _instructions(text) == _instructions(used)
    names = [n for _, n in _instructions(text)]
    # the update loop: a while at the program's top, around the rollout's
    assert any(re.fullmatch(r"jit\(looped\)/while", n) for n in names)
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    assert any("/while/body/" in n and "/rollout/" in n and "/env.step/" in n
               for n in names)
    assert any("/learner/loss_grad/" in n and "transpose(" in n
               for n in names)


def test_a_replaced_train_step_reaches_every_update():
    rl = _grid()
    seen = []
    step = rl._train_step

    def unchanged(params, opt_state, *rest):
        seen.append(None)  # runs while the loop is traced
        out = step(params, opt_state, *rest)
        return (params, opt_state) + tuple(out[2:])

    rl._train_step = unchanged
    before, key = jax.tree_util.tree_leaves(rl.params), rl.key
    res = rl.run(2 * UPDATES_PER_DISPATCH + 3)
    assert seen and res.dispatches == 3
    for a, b in zip(before, jax.tree_util.tree_leaves(rl.params)):
        assert bool(jnp.array_equal(a, b))
    # the rollouts still ran (the key moved on): only learning was dropped
    assert not bool(jnp.array_equal(key, rl.key))


def test_log_every_logs_at_dispatch_boundaries(jobs, caplog):
    rl = jobs("grid")
    with caplog.at_level(logging.INFO, logger="repro.framework"):
        logger = logging.getLogger("repro")
        propagate, logger.propagate = logger.propagate, True
        try:
            rl.run(20, log_every=5)
        finally:
            logger.propagate = propagate
    iters = [int(r.getMessage().split()[1]) for r in caplog.records
             if r.getMessage().startswith("iter ")]
    # boundaries at 8, 16, 20: past 5; past 10 and 15; at 20
    assert iters == [8, 16, 20]
