"""Find a cell's files by name.

``BENCHMARK.json`` (at the root of the checkout) lists the cells and
metrics. Everything that belongs to one of them is a file of its own under
``bench/``, found by the name alone:

* ``workloads/<cell>.json``  the cell: configuration, entry, sizes, limits;
* ``configs/<config>.json``  the configuration's published widths, and the
  model family it belongs to (its ``family`` key);
* ``families/<family>.py``   the family: the program's job, its FLOP count
  and its plain reference (``job``, ``flops_per_timestep``, ``train`` and
  ``FAULTS``);
* ``entries/<entry>.py``     how to build and drive one entry of the program;
* ``metrics/<metric>.py``    the reader of one metric.

Adding a cell, a configuration, a model family, an entry kind or a metric
adds a file and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import List, NamedTuple

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class Cell(NamedTuple):
    name: str
    workload: dict
    config: dict
    family: ModuleType
    entry: ModuleType


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def family(name: str) -> ModuleType:
    """The model family ``name``: ``families/<name>.py``."""
    return load_module(BENCH / "families" / f"{_checked(name)}.py")


def load_cell(name: str) -> Cell:
    workload = load_json(BENCH / "workloads" / f"{_checked(name)}.json")
    path = BENCH / "configs" / f"{_checked(workload['config'])}.json"
    config = load_json(path)
    if "family" not in config:
        raise ValueError(f"{path} names no model family (its 'family' key)")
    try:
        fam = family(config["family"])
    except FileNotFoundError as e:
        raise ValueError(f"{path}: family {config['family']!r} has no "
                         f"module {e}") from None
    entry = load_module(BENCH / "entries" / f"{_checked(workload['entry'])}.py")
    return Cell(name, workload, config, fam, entry)


def reader(metric: str) -> ModuleType:
    return load_module(BENCH / "metrics" / f"{_checked(metric)}.py")


def metrics_for(bench: dict, cell: str, traced: bool) -> List[str]:
    """The metrics a run of ``cell`` reports: the end-to-end ones untraced,
    the per-layer ones traced; a metric with a ``workloads`` list only in
    the cells it names."""
    kind = "per_layer" if traced else "end_to_end"
    return [m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])]
