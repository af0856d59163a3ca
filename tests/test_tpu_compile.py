"""Compile for the chip without the chip: the main path at its real widths.

The TPU compiler is installed wherever libtpu is, and compiles for a
described ``v5e:2x2`` topology that is not attached. This file compiles
what the chip runs — ``ParallelRL``'s train step, alone and looped as a
dispatch runs it, and the pipeline's fused-publish learner step for the
paper's ``paac_nature`` job (n_e=32, t_max=5), the sharded learner step
on a 4-chip mesh, and each Pallas
kernel at the widths of ``repro.kernels.cases`` — so that whatever the
chip's compiler refuses shows up here. Nothing runs; shapes come from
``jax.eval_shape``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file. Keep these tests in this one file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core.framework import init_rl_common, loop_train_step
from repro.core.rollout import make_collect_fn
from repro.distributed.sharding import (
    batch_sharding, replicated_sharding, traj_sharding,
)
from repro.kernels.cases import kernel_cases
from repro.optim import make_optimizer
from repro.pipeline.learner import make_learner_step, make_sharded_learner_step

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs outside
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _paper_job(n_envs):
    """Shapes of the paper's job and one of its rollouts (as chip_smoke
    builds it): (agent, lr, params, opt_state, env_state, obs, key, traj,
    last_obs)."""
    from repro.configs import get_config
    from repro.core.agents import PAACAgent, PAACConfig
    from repro.envs import AtariLike, FrameStack
    from repro.optim import constant

    env = FrameStack(AtariLike(n_envs), n=4)
    cfg = get_config("paac_nature").replace(obs_shape=env.obs_shape,
                                            num_actions=env.num_actions)
    agent = PAACAgent(cfg, PAACConfig(gamma=0.99, entropy_beta=0.01, t_max=5))
    lr = constant(0.0007 * n_envs)

    def init():
        *_, key, k_env, params, opt_state = init_rl_common(
            env, agent, "rmsprop", lr, 0)
        state = env.reset(k_env)
        obs = env.observe(state)
        _, last_obs, _, traj = make_collect_fn(
            agent.act_fn(), env, agent.hp.t_max)(params, state, obs, key)
        return params, opt_state, state, obs, key, traj, last_obs

    return (env, agent, lr) + tuple(jax.eval_shape(init))


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def test_parallel_rl_train_step_compiles(one_chip):
    env, agent, lr, params, opt_state, state, obs, key, _, _ = _paper_job(32)
    step = jax.jit(agent.make_train_step(env, make_optimizer("rmsprop"), lr))
    args = _on(one_chip, (params, opt_state, state, obs, key,
                          jax.ShapeDtypeStruct((), jnp.int32)))
    _fits_one_chip(step.lower(*args).compile())


def test_parallel_rl_multi_update_dispatch_compiles(one_chip):
    """The program a ``ParallelRL`` dispatch runs on the chip: the train
    step looped up to ``UPDATES_PER_DISPATCH`` times, the trip count an
    argument."""
    env, agent, lr, params, opt_state, state, obs, key, _, _ = _paper_job(32)
    step = jax.jit(agent.make_train_step(env, make_optimizer("rmsprop"), lr))
    counter = _on(one_chip, jax.ShapeDtypeStruct((), jnp.int32))
    carried = _on(one_chip, (params, opt_state, state, obs, key))
    keys = tuple(sorted(jax.eval_shape(step, *carried, counter)[-1]))
    loop = jax.jit(loop_train_step(step, keys))
    compiled = loop.lower(carried, counter, counter).compile()
    assert re.search(r'op_name="jit\(looped\)/while"', compiled.as_text())
    _fits_one_chip(compiled)


def test_fused_publish_learner_step_compiles(one_chip):
    _, agent, lr, params, opt_state, _, _, _, traj, last_obs = _paper_job(32)
    step = jax.jit(
        make_learner_step(agent, make_optimizer("rmsprop"), lr,
                          fused_publish=True),
        donate_argnums=(0, 1, 5),
    )
    args = _on(one_chip, (params, opt_state, traj, last_obs,
                          jax.ShapeDtypeStruct((), jnp.int32), params))
    _fits_one_chip(step.lower(*args).compile())


@pytest.mark.parametrize("case", kernel_cases(), ids=lambda c: c.name)
def test_kernel_compiles_to_a_tpu_custom_call(case, one_chip):
    args = _on(one_chip, jax.eval_shape(case.make, jax.random.PRNGKey(0)))
    fn = jax.jit(functools.partial(case.pallas, **case.kwargs,
                                   interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def test_sharded_learner_step_all_reduces_over_four_chips(topo):
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    assert mesh.size == 4
    _, agent, lr, params, opt_state, _, _, _, traj, last_obs = \
        _paper_job(4 * 32)
    step = make_sharded_learner_step(agent, make_optimizer("rmsprop"), lr,
                                     mesh)
    repl = replicated_sharding(mesh)
    traj = type(traj)(*(_on(traj_sharding(mesh, l.ndim), l) for l in traj))
    args = (_on(repl, params), _on(repl, opt_state), traj,
            _on(batch_sharding(mesh, last_obs.ndim), last_obs),
            _on(NamedSharding(mesh, P()), jax.ShapeDtypeStruct((), jnp.int32)),
            _on(repl, params))
    assert "all-reduce" in step.lower(*args).compile().as_text()


def test_held_expert_layer_compiles_to_grouped_matmul_kernels(one_chip,
                                                              monkeypatch):
    """DeepSeek-V2-Lite's expert layer as one chip of eight holds it (8 of
    64 experts, width 1408, top-6) on one acting step's 4096 tokens,
    forward and backward: the held experts' grouped matmuls are the
    megablox kernels."""
    from repro.configs import get_config
    from repro.models import moe

    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    cfg = get_config("deepseek-v2-lite").replace(experts_held=8,
                                                 experts_held_from=0)
    params = _on(one_chip, jax.eval_shape(
        lambda: moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    x = _on(one_chip, jax.ShapeDtypeStruct((8, 512, cfg.d_model),
                                           jnp.bfloat16))

    def loss(p, x):
        y, count = moe.moe_held_forward(p, cfg, x)
        return jnp.sum(y.astype(jnp.float32)), count

    step = jax.jit(jax.grad(loss, has_aux=True))
    compiled = step.lower(params, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 6  # 3 gmm forward, 3+3 backward
    kernels = re.findall(r'custom_call_target="tpu_custom_call".*?'
                         r'op_name="([^"]*)"', text)
    assert kernels and all("moe.experts" in k and "gmm" in k
                           for k in kernels), kernels
    _fits_one_chip(compiled)
