"""``BENCHMARK.json`` and the files the harness finds by name agree, and a
run without a TPU exits non-zero with no result."""
import json
import os
import subprocess
import sys

import pytest

from benchlib import cells, harness

SPEC = cells.spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [(kind, m) for kind in ("end_to_end", "per_layer")
           for m in SPEC[kind]]


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_has_its_files(name):
    cell = cells.load_cell(name)
    row = next(w for w in SPEC["workloads"] if w["name"] == name)
    assert row["config"] == cell.workload["config"]
    assert row["chips"] == cell.workload["chips"]
    assert row["why"] == cell.workload["why"]
    assert len(row["why"]) <= 200
    harness.check_lr_rule(cell.workload, cell.config)
    assert set(cell.workload["limits"]) == {"loss_gap", "grad_gap",
                                            "change_gap"}
    assert hasattr(cell.entry, "Entry")
    assert hasattr(cell.entry, "reference_layout")
    for name in ("job", "flops_per_timestep", "train", "FAULTS"):
        assert hasattr(cell.family, name), name


@pytest.mark.parametrize("kind, metric", METRICS,
                         ids=[m["name"] for _, m in METRICS])
def test_each_metric_has_a_reader_that_agrees(kind, metric):
    reader = cells.reader(metric["name"])
    assert reader.UNIT == metric["unit"]
    assert reader.SOURCE == metric["source"]
    assert reader.BETTER == metric["better"]
    if kind == "per_layer":
        assert reader.LAYER == metric["layer"]
        assert reader.MOVES == metric["moves"]
        assert set(metric["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("config", SPEC["configs"],
                         ids=[c["name"] for c in SPEC["configs"]])
def test_each_configuration_file_is_what_the_program_runs(config):
    data = cells.load_json(cells.ROOT / config["file"])
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    _, _, settings = cells.family(data["family"]).job(data, n_envs=2, t_max=5)
    harness.guard_widths(settings, data)


def test_a_width_the_program_does_not_run_is_refused():
    data = cells.load_json(cells.BENCH / "configs" / "paac_nature.json")
    _, _, settings = cells.family(data["family"]).job(data, n_envs=2, t_max=5)
    wrong = dict(data, dense=256)
    with pytest.raises(ValueError, match="dense"):
        harness.guard_widths(settings, wrong)


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_a_configuration_without_a_family_module_is_refused(
        family, tmp_path, monkeypatch):
    """No default family: a configuration that names none, or one that has
    no file, is refused with the configuration file's name."""
    config = cells.load_json(cells.BENCH / "configs" / "paac_nature.json")
    config.pop("family")
    if family is not None:
        config["family"] = family
    workload = dict(cells.load_cell("nature-sync-e32").workload,
                    config="orphan")
    for sub, name, data in (("configs", "orphan", config),
                            ("workloads", "orphan-cell", workload)):
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{name}.json").write_text(json.dumps(data))
    monkeypatch.setattr(cells, "BENCH", tmp_path)
    with pytest.raises(ValueError, match=r"configs/orphan\.json"):
        cells.load_cell("orphan-cell")


def test_metrics_for_a_cell():
    assert cells.metrics_for(SPEC, "nature-sync-e32", False) == [
        "timesteps_per_s", "setup_s"]
    spec = {"end_to_end": [], "per_layer": [
        {"name": "everywhere"},
        {"name": "mesh_only", "workloads": ["nature-mesh4-e32x4"]}]}
    assert cells.metrics_for(spec, "nature-mesh4-e32x4", True) == [
        "everywhere", "mesh_only"]
    assert cells.metrics_for(spec, "nature-sync-e32", True) == ["everywhere"]


@pytest.mark.parametrize("metric", ["learner_wait_share",
                                    "allreduce_ms_per_update"])
def test_mesh_readers_are_ready_for_the_mesh_cell(metric):
    """The mesh cell's own readers, kept for the PR that adds the cell."""
    reader = cells.reader(metric)
    assert reader.MOVES == "timesteps_per_s" and reader.UNIT


@pytest.mark.parametrize("bad", ["../etc", "a/b", "", "x" * 65])
def test_names_cannot_leave_their_directory(bad):
    with pytest.raises(ValueError):
        cells.load_cell(bad)


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(cells.BENCH / "run.py"), "--workload",
         "nature-sync-e32", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=str(cells.ROOT))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
