"""The trace reduction on a trace recorded on a TPU v5e.

``data/v5e-nature-sync-e32.xspace.txt`` is one update of the
``nature-sync-e32`` cell: a ``bench/run.py --trace 1 --keep-trace`` run cut
to 6 ms around its second ``train_step`` dispatch, with the chip's
``XLA Ops`` line (instruction names only) and the Python thread's host
line, written as a text XSpace.
"""
from pathlib import Path

import numpy as np
import pytest

from benchlib import trace as tr

DATA = Path(__file__).with_name("data") / "v5e-nature-sync-e32.xspace.txt"
LO, HI = 0.0, 6e6  # the cut, in ns


@pytest.fixture(scope="module")
def v5e():
    from jax.profiler import ProfileData

    return tr.from_profile(ProfileData.from_text_proto(DATA.read_text()))


def test_one_chip_and_the_window_annotation(v5e):
    assert list(v5e.devices) == [0]
    assert len(v5e.devices[0]) > 1000
    lo, hi = tr.span(v5e, "bench.window")
    assert lo < LO and hi > HI


def test_busy_matches_a_brute_force_union(v5e):
    ops = v5e.devices[0]
    covered = np.zeros(int(HI - LO), bool)
    for e in ops:
        s, t = int(max(e.start, LO) - LO), int(min(e.end, HI) - LO)
        if t > s:
            covered[s:t] = True
    assert tr.busy_ns(ops, LO, HI) == pytest.approx(covered.sum(), abs=len(ops))
    # one update of the paper's job keeps the chip busy about a millisecond
    assert 0.8e6 < tr.busy_ns(ops, LO, HI) < 1.5e6


def test_self_times_partition_the_busy_time(v5e):
    ops = v5e.devices[0]
    total = sum(tr.self_times(ops, LO, HI).values())
    assert total == pytest.approx(tr.busy_ns(ops, LO, HI), rel=1e-6)


def test_gaps_and_busy_fill_the_window(v5e):
    ops = v5e.devices[0]
    idle = sum(e - s for s, e in tr.gaps(ops, LO, HI))
    assert idle + tr.busy_ns(ops, LO, HI) == pytest.approx(HI - LO)


def test_the_long_gap_is_the_host_reading_metrics(v5e):
    """The finding this trace carries: after its ~1 ms of work the chip
    waits while the host converts the update's metric scalars."""
    name, seconds = tr.longest_gaps(v5e, LO, HI, top=1,
                                    skip=("bench.window",))[0]
    assert name == "np.asarray(jax.Array)"
    assert seconds > 3e-3


def test_no_collective_on_one_chip(v5e):
    assert tr.matching_ns(v5e.devices[0], LO, HI) == 0


def test_top_ops_are_instruction_names(v5e):
    ops = tr.top_ops(v5e, LO, HI)
    assert len(ops) == 10
    assert all(name.startswith("%") and " = " not in name for name, _ in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
