"""``correct`` on the CPU at a small n_e: a sound program passes; the
bfloat16 control and every fault a one-chip training cell can have fail.

The harness's look for a chip is skipped (``run_cell`` takes the devices),
the rest of a run is driven as on the chip, and each fault is planted in
the program underneath the entry the window drives.
"""
import math

import jax
import jax.numpy as jnp
import pytest

from benchlib import cells, check, harness

SEED = 2**31 + 7


def _small(name: str, n_envs: int = 4, t_max: int = 3):
    cell = cells.load_cell(name)
    wl = dict(cell.workload, n_envs=n_envs, t_max=t_max)
    wl["lr"] = cell.config["lr_per_env"] * n_envs * wl["lanes"]
    return cell._replace(workload=wl)


def _run(cell, plant=None):
    import time

    names = cells.metrics_for(cells.spec(), cell.name, False)
    return harness.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                            time.perf_counter(), names, plant=plant)


def _unchanged_state(entry):
    """The train step hands back the parameters and optimizer state it was
    given."""
    step = entry.rl._train_step

    def broken(params, opt_state, *rest):
        out = step(params, opt_state, *rest)
        return (params, opt_state) + tuple(out[2:])

    entry.rl._train_step = broken


def _half_batch(monkeypatch):
    """The loss takes its mean over the first half of the environments."""
    from repro.core.agents import paac
    from repro.core.rollout import Transition

    forward = paac.trajectory_forward

    def half(params, cfg, hp, traj, bootstrap):
        keep = traj.action.shape[1] // 2
        return forward(params, cfg, hp,
                       Transition(*(x[:, :keep] for x in traj)),
                       bootstrap[:keep])

    monkeypatch.setattr(paac, "trajectory_forward", half)


def _altered_action(monkeypatch):
    """Every sampled action is altered where it is drawn: the acting logits
    are rotated by one action."""
    from repro.core.agents import paac

    rollout = paac.rollout

    def altered(act_fn, *args):
        def act(params, obs):
            logits, value = act_fn(params, obs)
            return jnp.roll(logits, 1, axis=1), value
        return rollout(act, *args)

    monkeypatch.setattr(paac, "rollout", altered)


@pytest.fixture(scope="module")
def sound():
    return _run(_small("nature-sync-e32"))


def test_sound_program_is_correct(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] >= 1
    assert set(sound["metrics"]) == {"timesteps_per_s", "setup_s"}
    assert list(sound)[-1] == "check"
    for k in check.NUMBERS:
        assert sound["check"][k]["value"] <= sound["check"][k]["limit"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_action"])
def test_fault_in_the_program_is_not_correct(fault, monkeypatch):
    plant = None
    if fault == "unchanged_state":
        plant = _unchanged_state
    elif fault == "half_batch":
        _half_batch(monkeypatch)
    else:
        _altered_action(monkeypatch)
    result = _run(_small("nature-sync-e32"), plant=plant)
    assert result["correct"] is False, result["check"]


def test_bfloat16_control_is_not_correct():
    """The reference in the next precision below the configuration's, put
    in the program's place, fails the comparison."""
    cell = _small("nature-sync-e32")
    control = harness.reference_as_program(cell, SEED, dtype=jnp.bfloat16)
    numbers = harness.reference_readings(cell, SEED, control)
    assert not check.verdict(numbers, cell.workload["limits"]), numbers


def test_reference_in_the_programs_place_is_correct():
    cell = _small("nature-sync-e32")
    same = harness.reference_as_program(cell, SEED)
    numbers = harness.reference_readings(cell, SEED, same)
    assert numbers["loss_gap"] == 0.0
    assert numbers["change_gap"] == 0.0
    assert numbers["grad_gap"] < 1e-6  # the square and root of the state


def test_a_nan_loss_is_never_within_a_limit():
    assert not check.verdict({"loss_gap": math.nan, "grad_gap": 0.0,
                              "change_gap": 0.0},
                             {"loss_gap": 1.0, "grad_gap": 1.0,
                              "change_gap": 1.0})


def _readings(prog_losses, ref_losses):
    import numpy as np

    decay = 0.99
    grads = {"w": np.array([0.5, -0.5], np.float32)}
    before = {"w": np.zeros(2, np.float32)}
    after = {"w": np.ones(2, np.float32)}
    prog = {"losses": prog_losses, "params0": before, "params": after,
            "sq1": {"w": (1.0 - decay) * np.square(grads["w"])}}
    ref = {"losses": ref_losses, "grads": grads, "params0": before,
           "params": after}
    return check.readings(prog, ref, decay)


def test_loss_gap_is_the_first_updates():
    """The later updates learn from rollouts that have parted: only the
    first update's loss is compared."""
    assert _readings([2.0, 5.0, -3.0], [2.0, 1.0, 1.0])["loss_gap"] == 0.0
    assert _readings([2.2, 1.0, 1.0], [2.0, 1.0, 1.0])["loss_gap"] == \
        pytest.approx(0.1)


def test_a_later_loss_that_is_not_finite_fails():
    numbers = _readings([2.0, math.nan, 1.0], [2.0, 1.0, 1.0])
    assert numbers["loss_gap"] == math.inf
    assert not check.verdict(numbers, {"loss_gap": 1.0, "grad_gap": 1.0,
                                       "change_gap": 1.0})
