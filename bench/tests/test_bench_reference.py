"""The plain reference against the program's own ``ParallelRL`` on the CPU
at a small n_e, where both compute in full float32: the same seed gives the
same network, the same episodes and the same updates."""
import jax
import numpy as np
import pytest

from benchlib import cells, check, harness
from reference import atari, paac

SEED = 2**31 + 41  # a run's seed may need more than 32 signed bits


def _small(cell_name: str, n_envs: int):
    cell = cells.load_cell(cell_name)
    wl = dict(cell.workload, n_envs=n_envs)
    wl["lr"] = cell.config["lr_per_env"] * n_envs * wl["lanes"]
    return cell._replace(workload=wl)


@pytest.fixture(scope="module")
def nature_small():
    """The program and the reference over three updates at n_e=4."""
    cell = _small("nature-sync-e32", 4)
    cell = cell._replace(workload=dict(cell.workload, t_max=3))
    entry = cell.entry.Entry(cell.config, cell.workload, SEED, jax.devices()[:1])
    harness.guard_widths(entry.settings, cell.config)
    prog = harness.checked_updates(entry)
    ref = paac.train(cell.config, SEED, **cell.entry.reference_layout(cell.workload))
    return cell, prog, ref


def test_same_seed_same_network(nature_small):
    _, prog, ref = nature_small
    p0 = check._flat(prog["params0"])
    r0 = check._flat(ref["params0"])
    assert sorted(p0) == sorted(r0)
    for k in p0:
        np.testing.assert_array_equal(p0[k], r0[k], err_msg=k)


def test_same_losses_over_three_updates(nature_small):
    _, prog, ref = nature_small
    np.testing.assert_allclose(prog["losses"], ref["losses"], rtol=1e-5)


def test_same_parameters_after_three_updates(nature_small):
    _, prog, ref = nature_small
    p, r = check._flat(prog["params"]), check._flat(ref["params"])
    for k in p:
        np.testing.assert_allclose(p[k], r[k], rtol=1e-4, atol=1e-7, err_msg=k)


def test_readings_are_round_off_on_the_cpu(nature_small):
    cell, prog, ref = nature_small
    numbers = check.readings(prog, ref, cell.config["optimizer"]["decay"])
    assert numbers["loss_gap"] < 1e-5
    assert numbers["grad_gap"] < 1e-4
    assert numbers["change_gap"] < 1e-4
    assert check.verdict(numbers, cell.workload["limits"])


def test_game_matches_the_programs_game():
    """The copy of the pixel game steps exactly as the program's does."""
    import jax.numpy as jnp
    from repro.envs import AtariLike, FrameStack

    n = 6
    ours = atari.StackedGame(n)
    theirs = FrameStack(AtariLike(n), 4)
    key = jax.random.PRNGKey(SEED)
    k_reset, key = jax.random.split(key)
    s_ours, s_theirs = ours.reset(k_reset), theirs.reset(k_reset)
    step_ours, step_theirs = jax.jit(ours.step), jax.jit(theirs.step)
    scored = ended = False
    for _ in range(70):  # 5 balls of ~10 steps each: games end and restart
        key, k_a, k_env = jax.random.split(key, 3)
        actions = jax.random.randint(k_a, (n,), 0, 3)
        s_ours, o_ours, r_ours, d_ours = step_ours(s_ours, actions, k_env)
        s_theirs, o_theirs, r_theirs, d_theirs = step_theirs(
            s_theirs, actions, k_env)
        np.testing.assert_array_equal(o_ours, o_theirs)
        np.testing.assert_array_equal(r_ours, r_theirs)
        np.testing.assert_array_equal(d_ours, d_theirs)
        scored |= bool(jnp.any(r_ours != 0))
        ended |= bool(jnp.any(d_ours))
    assert scored and ended


def test_n_step_returns_by_hand():
    rewards = np.array([[1.0, 0.0], [0.0, -1.0], [0.0, 0.0]], np.float32)
    dones = np.array([[False, False], [True, False], [False, False]])
    boot = np.array([2.0, 4.0], np.float32)
    got = np.asarray(paac.n_step_returns(rewards, dones, boot, 0.5))
    # env 0: R2 = 0 + .5*2 = 1, R1 = 0 (done), R0 = 1 + .5*0 = 1
    # env 1: R2 = .5*4 = 2, R1 = -1 + .5*2 = 0, R0 = 0 + .5*0 = 0
    np.testing.assert_allclose(got, [[1.0, 0.0], [0.0, 0.0], [1.0, 2.0]])
