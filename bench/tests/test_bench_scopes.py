"""The program's own marks in a trace, on small traces written out by hand
(nanoseconds throughout): scope paths to layers, each op's self time to
one layer, the host loop's spans over the device's idle time, the
transfers inside the metric reads, the readers' silence on a program that
has neither, and on a scope map that is not the traced program's."""
import time
from types import SimpleNamespace

import pytest

from benchlib import cells, scopes
from benchlib import trace as tr

OP_NAMES = {
    "%while.1": "jit(train_step)/rollout/while",
    "%fusion.2": "jit(train_step)/rollout/while/body/closed_call/env.step/"
                 "vmap()/add",
    "%fusion.3": "jit(train_step)/rollout/while/body/closed_call/"
                 "policy.forward/conv_general_dilated",
    "%fusion.4": "jit(train_step)/learner/loss_grad/transpose(jvp())/dot",
    "%fusion.5": "jit(train_step)/learner/optimizer/sqrt",
}


def _ev(name, start, dur):
    return tr.Event(name, float(start), float(start + dur))


@pytest.mark.parametrize("op_name, found, layer", [
    ("jit(train_step)/rollout/while/body/closed_call/env.step/vmap()/add",
     ("rollout", "env.step"), "game"),
    ("jit(train_step)/rollout/while/body/policy.forward/dot_general",
     ("rollout", "policy.forward"), "acting"),
    ("jit(train_step)/rollout/while/body/dynamic_update_slice",
     ("rollout",), "acting"),
    ("jit(train_step)/learner/loss_grad/transpose(jvp())/mul",
     ("learner", "loss_grad"), "learner"),
    # a scope inside the backward pass's wrappers
    ("jit(train_step)/transpose(jvp(learner/loss_grad))/mul",
     ("learner", "loss_grad"), "learner"),
    ("jit(train_step)/learner/optimizer/sqrt;jit(train_step)/add",
     ("learner", "optimizer"), "learner"),
    ("jit(train_step)/while/body/closed_call/add", (), None),
    ("jit(rollout_like)/rolloutx/add", (), None),
    ("", (), None),
])
def test_scope_paths_name_one_layer(op_name, found, layer):
    assert scopes.scopes_in(op_name) == found
    assert scopes.layer_of(op_name) == layer


def test_op_names_come_from_the_hlo_text():
    text = "\n".join([
        "HloModule jit_train_step",
        "%fused_computation.1 (param_0: f32[4]) -> f32[4] {",
        '  ROOT %add.3 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/'
        'learner/add" source_file="x.py" source_line=3}',
        "}",
        "%region_0 (arg: f32[4]) -> f32[4] {",
        '  ROOT %add.4 = f32[4]{0} add(%arg, %arg), metadata={op_name="jit(f)/'
        'rollout/while/body/add"}',
        "}",
        "ENTRY %main.9 (p: f32[4]) -> f32[4] {",
        '  %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_'
        'computation.1, metadata={op_name="jit(f)/rollout/while/body/'
        'env.step/add"}',
        "  %copy.7 = f32[4]{0} copy(%fusion.2)",
        "}",
    ])
    # an instruction inside a fusion's computation never runs as an op;
    # one without metadata is in the program all the same
    assert scopes.hlo_op_names(text) == {
        "%add.4": "jit(f)/rollout/while/body/add",
        "%fusion.2": "jit(f)/rollout/while/body/env.step/add",
        "%copy.7": ""}


LINE = [
    _ev("%while.1", 0, 100),      # the acting scan
    _ev("%fusion.3", 5, 10),      # policy forward inside it
    _ev("%fusion.2", 20, 30),     # the game inside it
    _ev("%copy.9", 60, 10),       # no scope: the loop it runs in
    _ev("%fusion.4", 120, 40),    # the learner
    _ev("%copy.8", 170, 10),      # no scope, enclosed by nothing
    _ev("%fusion.5", 175, 20),    # the optimizer, overlapping the copy
]


def test_each_op_takes_its_own_or_its_loops_layer():
    layers = scopes.op_layers(LINE, OP_NAMES, 0, 1000)
    assert layers == {"%while.1": "acting", "%fusion.3": "acting",
                      "%fusion.2": "game", "%copy.9": "acting",
                      "%fusion.4": "learner", "%copy.8": None,
                      "%fusion.5": "learner"}


def test_layers_and_the_unscoped_rest_add_up_to_the_busy_time():
    split = scopes.layer_ns(LINE, OP_NAMES, 0, 1000)
    assert split == {"acting": 100 - 30, "game": 30, "learner": 40 + 20,
                     None: 5}
    assert sum(split.values()) == tr.busy_ns(LINE, 0, 1000)
    clipped = scopes.layer_ns(LINE, OP_NAMES, 30, 130)
    assert sum(clipped.values()) == tr.busy_ns(LINE, 30, 130) == 80


def test_covered_is_the_intersection_of_two_interval_lists():
    gaps = [(0, 10), (20, 40), (50, 60)]
    cover = [(5, 25), (35, 55), (70, 80)]
    assert scopes.covered_ns(gaps, cover) == 5 + 5 + 5 + 5
    assert scopes.covered_ns(gaps, []) == 0
    assert scopes.covered_ns([(0, 100)], [(0, 100)]) == 100


def _run(devices, host, updates=2, op_names=None):
    trace = tr.Trace({d: sorted(evs, key=lambda e: e.start)
                      for d, evs in devices.items()},
                     sorted(host, key=lambda e: e.start))
    run = SimpleNamespace(trace=trace, lo=0.0, hi=1000.0, updates=updates)
    run.program_probe = SimpleNamespace(op_names=op_names or {})
    return run


def _transfers(start, n):
    return [_ev(scopes.TRANSFER, start + 10 * i, 5) for i in range(n)]


# two updates: the chip busy in [100, 200) and [600, 700), the host loop's
# dispatch and metric read around each, six transfers in each read and one
# (not the driver's) in the first dispatch
HOST = ([_ev("bench.window", 0, 1000),
         _ev("step.dispatch", 50, 100), _ev("metrics.read", 150, 300),
         _ev("step.dispatch", 500, 150), _ev("metrics.read", 650, 250)]
        + _transfers(60, 1) + _transfers(160, 6) + _transfers(660, 6))
TWO_UPDATES = {0: [_ev("%fusion.2", 100, 50), _ev("%fusion.4", 150, 50),
                   _ev("%fusion.2", 600, 50), _ev("%fusion.4", 650, 50)],
               1: [_ev("%fusion.2", 100, 100), _ev("%fusion.4", 600, 100)]}


def test_host_spans_cover_the_idle_time_per_update():
    run = _run(TWO_UPDATES, HOST)
    # idle [0,100) [200,600) [700,1000) on both chips: dispatch covers
    # [50,100) and [500,600), the read [200,450) and [700,900)
    assert scopes.idle_covered_ms_per_update(run, "step.dispatch") == \
        pytest.approx(1e-6 * (50 + 100) / 2)
    assert scopes.idle_covered_ms_per_update(run, "metrics.read") == \
        pytest.approx(1e-6 * (250 + 200) / 2)


def test_readers_read_the_program_marks():
    run = _run(TWO_UPDATES, HOST, op_names=OP_NAMES)
    values = {name: cells.reader(name).read(run) for name in (
        "game_ms_per_update", "acting_ms_per_update",
        "learner_ms_per_update", "dispatch_idle_ms_per_update",
        "readback_idle_ms_per_update", "host_reads_per_update")}
    # chip 0: game 100, learner 100; chip 1: game 100 (its first op),
    # learner 100 -> per chip and per update
    assert values["game_ms_per_update"] == pytest.approx(1e-6 * 100 / 2)
    assert values["learner_ms_per_update"] == pytest.approx(1e-6 * 100 / 2)
    assert values["acting_ms_per_update"] == 0.0
    assert values["dispatch_idle_ms_per_update"] > 0
    assert values["readback_idle_ms_per_update"] > 0
    assert values["host_reads_per_update"] == 6.0


def test_a_program_without_scopes_or_spans_reads_as_nothing():
    """An older program: no span in the host plane, no scope in its HLO,
    no read count. Every new reader reports nothing and none raises."""
    run = _run(TWO_UPDATES, [_ev("bench.window", 0, 1000),
                             _ev("PjitFunction(train_step)", 50, 100)]
               + _transfers(160, 7),
               op_names={"%fusion.2": "jit(train_step)/while/body/add",
                         "%fusion.4": "jit(train_step)/add"})
    for name in ("game_ms_per_update", "acting_ms_per_update",
                 "learner_ms_per_update", "dispatch_idle_ms_per_update",
                 "readback_idle_ms_per_update", "host_reads_per_update"):
        assert cells.reader(name).read(run) is None, name


def test_a_scope_map_that_is_not_the_traced_program_reads_as_nothing(
        capsys):
    """Where the trace's ops are not the map's instructions (a map from
    another compile), the layer readers report nothing and say why; a
    sliver of foreign ops (the eager step counter's) is let through."""
    foreign = {0: [_ev("%fusion.2", 100, 50), _ev("%fusion.77", 150, 50)]}
    run = _run(foreign, HOST, op_names=OP_NAMES)
    for name in ("game_ms_per_update", "acting_ms_per_update",
                 "learner_ms_per_update"):
        assert cells.reader(name).read(run) is None, name
    err = capsys.readouterr().err
    assert "reader failed" in err and "50.00%" in err
    sliver = {0: [_ev("%fusion.2", 100, 995), _ev("%add.1", 1095, 5)]}
    run = _run(sliver, HOST, op_names=OP_NAMES)
    run.hi = 2000.0
    assert scopes.unmapped_share(tr.self_times(sliver[0], 0, 2000),
                                 OP_NAMES) == pytest.approx(0.005)
    assert scopes.layer_split(run) == pytest.approx(
        {"game": 1e-6 * 995 / 2, None: 1e-6 * 5 / 2})


def test_transfers_are_counted_inside_the_metric_reads_only():
    run = _run(TWO_UPDATES, HOST)
    assert scopes.host_reads_per_update(run) == 6.0
    # a read cut by the window's end counts the transfers inside the window
    run.hi = 680.0
    assert scopes.host_reads_per_update(run) == (6 + 2) / 2


def test_a_scope_map_that_is_not_the_traced_program_reads_as_nothing(
        capsys):
    """Where the trace's ops are not the map's instructions (a map from
    another compile), the layer readers report nothing and say why; a
    sliver of foreign ops (the eager step counter's) is let through."""
    foreign = {0: [_ev("%fusion.2", 100, 50), _ev("%fusion.77", 150, 50)]}
    run = _run(foreign, HOST, op_names=OP_NAMES)
    for name in ("game_ms_per_update", "acting_ms_per_update",
                 "learner_ms_per_update"):
        assert cells.reader(name).read(run) is None, name
    err = capsys.readouterr().err
    assert "reader failed" in err and "50.00%" in err
    sliver = {0: [_ev("%fusion.2", 100, 995), _ev("%add.1", 1095, 5)]}
    run = _run(sliver, HOST, op_names=OP_NAMES)
    run.hi = 2000.0
    assert scopes.unmapped_share(tr.self_times(sliver[0], 0, 2000),
                                 OP_NAMES) == pytest.approx(0.005)
    assert scopes.layer_split(run) == pytest.approx(
        {"game": 1e-6 * 995 / 2, None: 1e-6 * 5 / 2})


def test_transfers_are_counted_inside_the_metric_reads_only():
    run = _run(TWO_UPDATES, HOST)
    assert scopes.host_reads_per_update(run) == 6.0
    # a read cut by the window's end counts the transfers inside the window
    run.hi = 680.0
    assert scopes.host_reads_per_update(run) == (6 + 2) / 2


def test_probe_reads_the_compiled_sync_step_and_its_transfers(tmp_path):
    """The probe rebuilds the cell's entry (here at a small n_e, on the
    CPU) and reads its compiled train step; the entry's own profile holds
    six transfers per update inside its metric reads."""
    import jax

    cell = cells.load_cell("nips-sync-e32")
    wl = dict(cell.workload, n_envs=2, t_max=2)
    run = SimpleNamespace(workload=wl, config=cell.config, chips=1)
    found = scopes.probe(run)
    assert scopes.probe(run) is found  # once per run
    layers = {scopes.layer_of(p) for p in found.op_names.values()}
    assert {"game", "acting", "learner"} <= layers

    entry = cell.entry.Entry(cell.config, wl, 1, jax.devices()[:1])
    try:
        entry.run(1)
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation("bench.window"):
                entry.run(3)
    finally:
        entry.close()
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    lo, hi = tr.span(trace, "bench.window")
    window = SimpleNamespace(trace=trace, lo=lo, hi=hi, updates=3)
    assert cells.reader("host_reads_per_update").read(window) == 6.0


def test_probe_of_an_entry_without_a_compiled_step_is_empty(monkeypatch):
    class Entry:
        def __init__(self, *args):
            self.rl = object()
            self.closed = False

        def close(self):
            Entry.closed = True

    monkeypatch.setattr(cells, "load_module",
                        lambda path: SimpleNamespace(Entry=Entry))
    run = SimpleNamespace(workload={"entry": "other"}, config={}, chips=1)
    found = scopes.probe(run)
    assert found.op_names == {}
    assert Entry.closed


def test_probe_that_fails_reports_nothing_and_does_not_raise(monkeypatch,
                                                             capsys):
    def broken(path):
        raise RuntimeError("no such entry")

    monkeypatch.setattr(cells, "load_module", broken)
    run = SimpleNamespace(workload={"entry": "other"}, config={}, chips=1)
    found = scopes.probe(run)
    assert found.op_names == {}
    err = capsys.readouterr().err
    assert "reader failed" in err and "no such entry" in err
