"""Trace reduction: device busy time, op self times, collectives and idle
gaps, on small traces written out by hand (nanoseconds throughout)."""
import pytest

from benchlib import trace as tr


def _xspace(devices, host):
    """A text-format XSpace: ``devices`` maps chip id -> [(name, start,
    dur)] on its ``XLA Ops`` line, ``host`` is [(name, start, dur)] on one
    host thread. Times in ns."""
    names = sorted({n for evs in list(devices.values()) + [host]
                    for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f' event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                   for n, i in ids.items())

    def line(name, evs):
        body = "".join(f" events {{ metadata_id: {ids[n]} offset_ps: {s * 1000}"
                       f" duration_ps: {d * 1000} }}" for n, s, d in evs)
        return f' lines {{ id: 1 name: "{name}" timestamp_ns: 0{body} }}'

    planes = [f'planes {{ id: {10 + dev} name: "/device:TPU:{dev}"'
              f'{line("XLA Ops", evs)}{meta} }}'
              for dev, evs in devices.items()]
    planes.append(f'planes {{ id: 1 name: "/host:CPU"{line("python", host)}{meta} }}')
    return "\n".join(planes)


def _trace(devices, host):
    from jax.profiler import ProfileData

    return tr.from_profile(ProfileData.from_text_proto(_xspace(devices, host)))


TWO_CHIPS = {
    0: [("fusion.1", 100, 300), ("all-reduce.2", 450, 50), ("fusion.3", 700, 100)],
    1: [("fusion.1", 120, 300), ("all-reduce.2", 450, 100), ("fusion.3", 900, 50)],
}
HOST = [("bench.window", 0, 1000), ("PjitFunction(train_step)", 80, 30),
        ("queue.get", 550, 140), ("publish", 820, 60)]


@pytest.fixture
def two_chips():
    return _trace(TWO_CHIPS, HOST)


def test_planes_become_chips_and_host_events(two_chips):
    assert sorted(two_chips.devices) == [0, 1]
    assert [e.name for e in two_chips.devices[0]] == ["fusion.1", "all-reduce.2",
                                                      "fusion.3"]
    assert tr.span(two_chips, "bench.window") == (0.0, 1000.0)
    assert tr.span(two_chips, "missing") is None


@pytest.mark.parametrize("chip, lo, hi, busy", [
    (0, 0, 1000, 450),   # 300 + 50 + 100
    (1, 0, 1000, 450),   # 300 + 100 + 50
    (0, 200, 460, 210),  # clipped: 200 of fusion.1, 10 of the all-reduce
    (1, 1000, 2000, 0),
])
def test_busy_is_the_clipped_union(two_chips, chip, lo, hi, busy):
    assert tr.busy_ns(two_chips.devices[chip], lo, hi) == busy


def test_overlapping_and_nested_ops_are_counted_once():
    t = _trace({0: [("while.1", 0, 100), ("fusion.2", 10, 20),
                    ("fusion.3", 50, 80), ("copy.4", 200, 10)]}, [])
    ops = t.devices[0]
    assert tr.busy_ns(ops, 0, 1000) == 140  # [0, 130) and [200, 210)
    assert tr.merged(ops, 0, 1000) == [(0, 130), (200, 210)]
    # self time: the loop keeps what its body does not cover
    assert tr.self_times(ops, 0, 1000) == {
        "while.1": 100 - 20 - 50, "fusion.2": 20, "fusion.3": 80, "copy.4": 10}


def test_ops_that_overlap_without_nesting_are_counted_once():
    """A TPU line also holds ops that overlap without nesting; the self
    times still add up to the busy time."""
    t = _trace({0: [("while.1", 0, 100), ("fusion.2", 10, 10),
                    ("copy.3", 15, 15), ("fusion.4", 110, 10)]}, [])
    ops = t.devices[0]
    times = tr.self_times(ops, 0, 1000)
    assert sum(times.values()) == tr.busy_ns(ops, 0, 1000) == 110
    assert times == {"while.1": 10 + 70, "fusion.2": 5, "copy.3": 15,
                     "fusion.4": 10}


def test_all_reduce_time_per_chip(two_chips):
    assert tr.matching_ns(two_chips.devices[0], 0, 1000) == 50
    assert tr.matching_ns(two_chips.devices[1], 0, 1000) == 100
    assert tr.matching_ns(two_chips.devices[0], 0, 400) == 0


def test_gaps_complement_the_busy_time(two_chips):
    ops = two_chips.devices[0]
    gaps = tr.gaps(ops, 0, 1000)
    assert gaps == [(0, 100), (400, 450), (500, 700), (800, 1000)]
    assert sum(e - s for s, e in gaps) + tr.busy_ns(ops, 0, 1000) == 1000


def test_longest_gaps_are_named_by_the_host(two_chips):
    gaps = tr.longest_gaps(two_chips, 0, 1000, top=3, skip=("bench.window",))
    # chip 0 [500, 700) and chip 1 [550, 900) overlap queue.get most
    assert gaps[0] == ("tpu1: queue.get", pytest.approx(350e-9))
    assert gaps[1][1] == pytest.approx(200e-9)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_a_gap_with_no_host_event_says_so():
    t = _trace({0: [("fusion.1", 0, 10)]}, [("bench.window", 0, 100)])
    assert tr.longest_gaps(t, 0, 100, skip=("bench.window",)) == [
        ("no host event", pytest.approx(90e-9))]


def test_top_ops_average_over_chips(two_chips):
    ops = dict(tr.top_ops(two_chips, 0, 1000))
    assert ops["fusion.1"] == pytest.approx(300e-9)
    assert ops["all-reduce.2"] == pytest.approx(75e-9)
    assert ops["fusion.3"] == pytest.approx(75e-9)
    assert list(ops)[0] == "fusion.1"
