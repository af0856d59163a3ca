"""The job, the FLOP count and the reference come from the configuration's
model family (``families/<family>.py``).

The ``paac_cnn`` family gives what the harness used before it looked the
family up, bit for bit; and a second family, with a configuration that
has none of ``paac_cnn``'s width keys, joins a copy of ``bench/`` by new
files alone and runs through ``harness.run_cell`` to a ``correct`` result.
"""
import hashlib
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchlib import cells, chip, flops, harness
from benchlib.paper_job import paper_job
from reference import paac

SEED = 2**31 + 29
CELL_OF = {"paac_nature": "nature-sync-e32", "paac_nips": "nips-sync-e32"}
SECOND = Path(__file__).with_name("data") / "second_family"
SECOND_CELL = "nips-own-keys-e4"


@pytest.mark.parametrize("config, per_frame", [("paac_nature", 18_690_048),
                                               ("paac_nips", 5_933_056)])
def test_family_flops_are_the_hand_count(config, per_frame):
    cell = cells.load_cell(CELL_OF[config])
    t_max = cell.workload["t_max"]
    got = cell.family.flops_per_timestep(cell.config, t_max)
    assert got == flops.flops_per_timestep(cell.config, t_max)
    assert got == per_frame * (3.0 + 1.0 / t_max)


@pytest.mark.parametrize("config", sorted(CELL_OF))
def test_family_reference_is_bit_identical(config):
    cell = cells.load_cell(CELL_OF[config])
    layout = dict(cell.entry.reference_layout(cell.workload), n_envs=2,
                  t_max=3)
    ours = cell.family.train(cell.config, SEED, **layout)
    plain = paac.train(cell.config, SEED, **layout)
    assert ours["losses"] == plain["losses"]
    for key in ("grads", "params0", "params"):
        a, b = jax.tree_util.tree_leaves_with_path(ours[key]), \
            jax.tree_util.tree_leaves_with_path(plain[key])
        assert [p for p, _ in a] == [p for p, _ in b]
        for (path, x), (_, y) in zip(a, b):
            np.testing.assert_array_equal(x, y, err_msg=f"{key} {path}")


@pytest.mark.parametrize("config", sorted(CELL_OF))
def test_family_job_is_the_paper_job(config):
    cell = cells.load_cell(CELL_OF[config])
    env, agent, settings = cell.family.job(cell.config, 2, 5)
    env0, agent0, settings0 = paper_job(cell.config, 2, 5)
    assert settings == settings0
    assert (type(env), env.obs_shape, env.num_actions, env.n_envs) == \
        (type(env0), env0.obs_shape, env0.num_actions, env0.n_envs)
    assert agent.cfg == agent0.cfg and agent.hp == agent0.hp


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.fixture(scope="module")
def second(tmp_path_factory):
    """A copy of ``bench/`` with the second family's three files added,
    ``cells`` pointed at it, and one run of its cell on a CPU device."""
    copy = tmp_path_factory.mktemp("checkout") / "bench"
    shutil.copytree(cells.BENCH, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(copy)
    added = []
    for src in sorted(p for p in SECOND.rglob("*") if p.is_file()):
        dst = copy / src.relative_to(SECOND)
        assert not dst.exists(), dst
        shutil.copy(src, dst)
        added.append(dst)
    seen = []
    real_reader = cells.reader

    def spying(name):
        reader = real_reader(name)

        def read(ctx):
            seen.append(ctx)
            return reader.read(ctx)
        return SimpleNamespace(UNIT=reader.UNIT, read=read)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cells, "BENCH", copy)
        mp.setattr(cells, "ROOT", copy.parent)
        mp.setattr(cells, "reader", spying)
        cell = cells.load_cell(SECOND_CELL)
        result = harness.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                                  time.perf_counter(),
                                  ["timesteps_per_s", "setup_s"])
    return SimpleNamespace(copy=copy, before=before, added=added, cell=cell,
                           result=result, ctx=seen[0])


def test_second_family_configuration_has_no_paac_cnn_widths(second):
    config = second.cell.config
    assert not {"obs_shape", "convs", "dense"} & set(config)
    assert second.cell.family.__file__ == str(
        second.copy / "families" / "cnn_own_keys.py")
    with pytest.raises(KeyError):
        flops.flops_per_timestep(config, second.cell.workload["t_max"])


def test_second_family_runs_correct(second):
    result = second.result
    assert result["correct"] is True, result["check"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"timesteps_per_s", "setup_s"}


def test_second_family_counts_the_flops_behind_mfu(second):
    cell, ctx = second.cell, second.ctx
    own = cell.family.flops_per_timestep(cell.config, cell.workload["t_max"])
    assert ctx.flops_per_timestep == own
    kind = "TPU v5 lite"
    traced = SimpleNamespace(**dict(vars(ctx), trace=object(),
                                    trace_window_s=2.0, device_kind=kind))
    peak = chip.peaks(kind)["bf16_flops_per_s"]
    assert cells.reader("mfu").read(traced) == pytest.approx(
        100.0 * own * ctx.timesteps / (2.0 * ctx.chips * peak), rel=1e-12)


def test_second_family_adds_files_and_edits_none(second):
    after = _digests(second.copy)
    assert {k: after.get(k) for k in second.before} == second.before
    rel = {str(p.relative_to(second.copy)) for p in second.added}
    assert rel == {"families/cnn_own_keys.py", "configs/nips_own_keys.json",
                   f"workloads/{SECOND_CELL}.json"}
