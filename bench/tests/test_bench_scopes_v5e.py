"""The scope readers on a trace recorded on a TPU v5e.

``data/v5e-nature-sync-e32.xspace.txt`` is one update of the
``nature-sync-e32`` cell recorded on the chip (see
``test_bench_trace_v5e.py``), from a program with no spans.
``data/v5e-nature-sync-e32-scopes.json`` is the scope map (instruction
name -> ``op_name``) of the cell's train step compiled with its named
scopes for a v5e described without the chip, cut to the instructions
that run in the recording. The scopes change only metadata, so the
instruction names are those the chip ran: the map names every recorded
op.
"""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchlib import cells, scopes
from benchlib import trace as tr

DATA = Path(__file__).with_name("data")
XSPACE = DATA / "v5e-nature-sync-e32.xspace.txt"
SCOPE_MAP = DATA / "v5e-nature-sync-e32-scopes.json"
LO, HI = 0.0, 6e6  # the cut, in ns
LAYER_READERS = ("game_ms_per_update", "acting_ms_per_update",
                 "learner_ms_per_update")
SPAN_READERS = ("dispatch_idle_ms_per_update", "readback_idle_ms_per_update",
                "host_reads_per_update")


@pytest.fixture(scope="module")
def v5e():
    from jax.profiler import ProfileData

    return tr.from_profile(ProfileData.from_text_proto(XSPACE.read_text()))


@pytest.fixture
def run(v5e):
    """The reader context of a traced run over the cut: one update."""
    busy = tr.busy_ns(v5e.devices[0], LO, HI) * 1e-9
    ctx = SimpleNamespace(trace=v5e, lo=LO, hi=HI, updates=1, busy_s=busy,
                          trace_window_s=(HI - LO) * 1e-9)
    ctx.program_probe = SimpleNamespace(
        op_names=json.loads(SCOPE_MAP.read_text()))
    return ctx


def test_the_map_names_every_recorded_op(run):
    times = tr.self_times(run.trace.devices[0], LO, HI)
    assert scopes.unmapped_share(times, run.program_probe.op_names) == 0.0


def test_scoped_and_unscoped_add_up_to_the_busy_time(run):
    split = scopes.layer_split(run)
    busy_ms = cells.reader("busy_ms_per_update").read(run)
    assert set(split) == {"game", "acting", "learner", None}
    assert sum(split.values()) == pytest.approx(busy_ms, rel=1e-9)
    values = [cells.reader(name).read(run) for name in LAYER_READERS]
    assert sum(values) == pytest.approx(busy_ms - split[None], rel=1e-9)
    # the issue's bar: the three layers own at least 90% of the busy time
    assert sum(values) >= 0.9 * busy_ms


def test_the_game_owns_the_busiest_ops(run):
    """The recording's two ops with most self time are the game's
    (its dynamics' loop under ``env.step``); the conv forward of the
    acting policy and the learner's backward conv follow."""
    layers = scopes.op_layers(run.trace.devices[0], run.program_probe.op_names,
                              LO, HI)
    ranked = [name for name, _ in tr.top_ops(run.trace, LO, HI, top=4)]
    assert [layers[n] for n in ranked] == ["game", "game", "acting",
                                           "learner"]


def test_span_readers_read_nothing_on_a_program_without_spans(run):
    for name in SPAN_READERS:
        assert cells.reader(name).read(run) is None, name
