"""The synchronous driver's measurement: its spans, its read count, its
profiler mirroring, and the train step's named scopes.

Pins:

* ``ParallelRL.run`` records one ``step.dispatch`` and one
  ``metrics.read`` span per dispatch, a chunk of up to
  ``UPDATES_PER_DISPATCH`` updates on the fused path (the read folds the
  previous chunk's packed metrics after the next dispatch, the last
  chunk's after the loop); under ``jax.profiler`` each lands in the
  profile's host plane, with durations equal to the emitter's totals
  (``RunResult.dispatch_s`` / ``readback_s``) within 2% — the spans and
  the device trace share one clock — beside one ``train`` step
  annotation per dispatch,
* with the profiler off no ``TraceAnnotation`` is built, and the new
  ``RunResult`` fields are still filled (``host_reads``: one transfer per
  dispatch on the fused path, per device metric scalar on the
  ``HostEnvPool`` path, each read once),
* under the profiler each ``metrics.read`` span holds one
  ``np.asarray(jax.Array)`` event per transfer, and no other span does,
* ``record()`` spans stay out of the profile, ``begin``/``end`` spans do
  not,
* the two new categories are appended: every older category keeps its
  index (shipped rings carry indices),
* the train step's HLO carries every named scope in its ``op_name``
  metadata, fused and pipelined alike,
* ``launch/train.py --trace`` writes the driver's track without
  ``--pipeline``.
"""
import glob
import json

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import ParallelRL
from repro.core.agents import PAACAgent, PAACConfig
from repro.core.framework import UPDATES_PER_DISPATCH
from repro.envs import AtariLike, FrameStack, GridWorld, py_bound_spec
from repro.optim import constant
from repro.telemetry import (
    CATEGORIES,
    COLLECT,
    METRICS_READ,
    PUBLISH,
    STEP_DISPATCH,
    SpanEmitter,
)

# the vocabulary as it shipped before the driver's two stages
OLD_CATEGORIES = (
    "collect", "queue.put_wait", "queue.get_wait", "lease", "publish",
    "learner.update", "shm.copy", "mesh.reassemble", "replay.add",
    "replay.sample", "replay.evict", "fault.detect", "fault.respawn",
    "fault.giveup",
)
SCOPES = ("rollout", "policy.forward", "env.step", "learner", "loss_grad",
          "optimizer")
# the PAAC train step's metric scalars, one transfer each per update on
# the HostEnvPool path (the fused path packs them: one per dispatch)
PAAC_METRICS = 6


def _dispatches(n):
    return -(-n // UPDATES_PER_DISPATCH)


def _grid_rl(seed=0):
    env = GridWorld(8, size=4, max_steps=20)
    cfg = get_config("paac_vector").replace(obs_shape=env.obs_shape,
                                            num_actions=env.num_actions)
    return ParallelRL(env, PAACAgent(cfg, PAACConfig(t_max=3)),
                      lr_schedule=constant(1e-3), seed=seed)


def _host_events(log_dir):
    from jax.profiler import ProfileData

    path = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    data = ProfileData.from_file(path)
    return [e for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


def test_driver_categories_are_appended():
    assert CATEGORIES[:len(OLD_CATEGORIES)] == OLD_CATEGORIES
    assert CATEGORIES[STEP_DISPATCH] == "step.dispatch"
    assert CATEGORIES[METRICS_READ] == "metrics.read"
    assert (STEP_DISPATCH, METRICS_READ) == (len(OLD_CATEGORIES),
                                             len(OLD_CATEGORIES) + 1)


def test_driver_spans_land_in_the_profile_on_its_clock(tmp_path):
    rl = _grid_rl()
    rl.run(2)  # compile outside the profile
    # 30 spans of each kind, as many as one update a dispatch made: the
    # 2% match of the totals needs spans enough to average the profiler's
    # per-span jitter out; the last chunk is short
    updates = 30 * UPDATES_PER_DISPATCH - 3
    with jax.profiler.trace(str(tmp_path)):
        res = rl.run(updates)
    events = _host_events(tmp_path)
    dispatch = [e for e in events if e.name == "step.dispatch"]
    read = [e for e in events if e.name == "metrics.read"]
    steps = [e for e in events if e.name == "train"]
    assert len(dispatch) == len(read) == len(steps) == _dispatches(updates)
    assert len(dispatch) == res.dispatches == 30
    # each step annotation is numbered by its chunk's first update
    assert sorted(dict(e.stats)["step_num"] for e in steps) == list(
        range(2, 2 + updates, UPDATES_PER_DISPATCH))
    assert sum(e.duration_ns for e in dispatch) * 1e-9 == pytest.approx(
        res.dispatch_s, rel=0.02)
    assert sum(e.duration_ns for e in read) * 1e-9 == pytest.approx(
        res.readback_s, rel=0.02)
    # the same totals the driver's emitter keeps
    driver = rl.telemetry.tracks()[0][2]
    assert driver.name == "driver"
    assert driver.total(STEP_DISPATCH) == res.dispatch_s
    assert driver.total(METRICS_READ) == res.readback_s


def test_each_transfer_is_one_asarray_event_inside_metrics_read(tmp_path):
    rl = _grid_rl()
    rl.run(2)
    updates = 20
    with jax.profiler.trace(str(tmp_path)):
        res = rl.run(updates)
    events = _host_events(tmp_path)
    reads = [(e.start_ns, e.start_ns + e.duration_ns) for e in events
             if e.name == "metrics.read"]
    asarray = [e for e in events if e.name == "np.asarray(jax.Array)"]
    inside = [e for e in asarray if any(
        s <= e.start_ns and e.start_ns + e.duration_ns <= t for s, t in reads)]
    assert res.host_reads == _dispatches(updates) == 3
    assert len(inside) == res.host_reads


def test_no_annotation_is_built_with_the_profiler_off(monkeypatch):
    class Refused(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            raise AssertionError("annotation built with the profiler off")

    class RefusedStep(jax.profiler.StepTraceAnnotation):
        def __init__(self, *a, **kw):
            raise AssertionError("step annotation built with the profiler off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", RefusedStep)
    rl = _grid_rl()
    res = rl.run(12)
    assert res.dispatch_s > 0 and res.readback_s > 0
    assert res.host_reads == res.dispatches == _dispatches(12)


def test_record_spans_stay_out_of_the_profile(tmp_path):
    em = SpanEmitter("t")
    with jax.profiler.trace(str(tmp_path)):
        em.begin(COLLECT)
        em.end()
        em.record(PUBLISH, 1.0, 2.0)
        em.begin(STEP_DISPATCH)
        em.cancel()  # a cancelled span still closes its annotation
        em.begin(METRICS_READ)
        em.end()
    names = [e.name for e in _host_events(tmp_path)]
    assert names.count("collect") == 1
    assert names.count("metrics.read") == 1
    assert "publish" not in names
    assert [CATEGORIES[c] for c, _, _ in em.snapshot()] == [
        "collect", "publish", "metrics.read"]


def test_host_reads_count_transfers_not_float_calls():
    from repro.core.framework import MetricsAccumulator

    acc = MetricsAccumulator()
    acc.update({"loss": jnp.float32(1.5), "episodes": jnp.float32(2.0),
                "host": 3.0})
    assert acc.host_reads == 2  # episodes feeds two sums, read once
    assert acc.episodes == 2.0
    assert acc.result(1, 1).host_reads == 2


def test_a_packed_fold_is_one_transfer_and_folds_as_the_dicts():
    from repro.core.framework import MetricsAccumulator, PackedMetrics

    keys = ("count.x", "episodes", "loss")
    rows = jnp.asarray([[3.0, 1.0, 0.25], [4.0, 0.0, 1.0 / 3.0],
                        [9.0, 9.0, 9.0]], jnp.float32)
    packed, dicts = MetricsAccumulator(), MetricsAccumulator()
    packed.update(PackedMetrics(rows, keys, 2))
    for row in rows[:2]:
        dicts.update(dict(zip(keys, row)))
    assert packed.host_reads == 1 and dicts.host_reads == 6
    assert packed.acc == dicts.acc and packed.iters == dicts.iters == 2
    assert packed.episodes == dicts.episodes == 1.0
    assert packed.last("loss") == dicts.last("loss")


def test_host_env_path_spans_collect_dispatch_and_read():
    pool = py_bound_spec(4, obs_dim=4, spin=0, n_workers=2).build()
    try:
        cfg = get_config("paac_vector").replace(obs_shape=(4,), num_actions=3)
        rl = ParallelRL(pool, PAACAgent(cfg, PAACConfig(t_max=2)),
                        lr_schedule=constant(1e-3), seed=0)
        res = rl.run(3)
    finally:
        pool.close()
    driver = rl.telemetry.tracks()[0][2]
    # the host rollout sits outside the driver's spans: no collect span
    assert driver.total(COLLECT) == 0
    assert [CATEGORIES[c] for c, _, _ in driver.snapshot()] == [
        "step.dispatch", "metrics.read"] * 3
    assert res.dispatch_s == driver.total(STEP_DISPATCH) > 0
    # the shared learner step adds rho_mean, rho_clip_frac, c_clip_frac
    assert res.host_reads == (PAAC_METRICS + 3) * 3


def _op_names(hlo_text):
    import re

    return re.findall(r'op_name="([^"]*)"', hlo_text)


def test_nature_train_step_carries_every_scope():
    env = FrameStack(AtariLike(4), n=4)
    cfg = get_config("paac_nature").replace(obs_shape=env.obs_shape,
                                            num_actions=env.num_actions)
    rl = ParallelRL(env, PAACAgent(cfg, PAACConfig(t_max=2)),
                    lr_schedule=constant(1e-3), seed=0)
    names = _op_names(rl.compiled().as_text())
    for scope in SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    assert any("/rollout/" in n and "/env.step/" in n for n in names)
    assert any("/learner/loss_grad/" in n and "transpose(" in n
               for n in names)
    assert any("/learner/optimizer/" in n for n in names)


def test_pipelined_learner_step_carries_the_learner_scopes():
    from repro.optim import make_optimizer
    from repro.pipeline.learner import make_learner_step
    from repro.core.rollout import make_collect_fn

    env = GridWorld(4, size=4, max_steps=20)
    cfg = get_config("paac_vector").replace(obs_shape=env.obs_shape,
                                            num_actions=env.num_actions)
    agent = PAACAgent(cfg, PAACConfig(t_max=2))
    rl = ParallelRL(env, agent, lr_schedule=constant(1e-3), seed=0)
    collect = make_collect_fn(agent.act_fn(), env, 2)
    _, last_obs, _, traj = collect(rl.params, rl.env_state, rl.obs, rl.key)
    opt = make_optimizer("rmsprop")
    step = jax.jit(make_learner_step(agent, opt, constant(1e-3)))
    text = step.lower(rl.params, rl.opt_state, traj, last_obs,
                      jnp.asarray(0, jnp.int32)).compile().as_text()
    names = _op_names(text)
    for scope in ("learner", "loss_grad", "optimizer"):
        assert any(f"/{scope}/" in n for n in names), scope


def test_train_launcher_traces_the_sync_driver(tmp_path):
    from repro.launch import train

    path = tmp_path / "sync_trace.json"
    train.main(["--arch", "paac_vector", "--iterations", "17", "--n-envs",
                "2", "--t-max", "2", "--ctx", "8", "--trace", str(path)])
    events = json.loads(path.read_text())["traceEvents"]
    tracks = {e["args"]["name"] for e in events
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks == {"driver"}
    names = [e["name"] for e in events if e["ph"] == "X"]
    # one span each per dispatch of up to UPDATES_PER_DISPATCH updates
    assert names.count("step.dispatch") == names.count("metrics.read") == \
        _dispatches(17)


@pytest.mark.parametrize("flag", [["--metrics-jsonl", "hb.jsonl"],
                                  ["--stall-timeout", "5"]])
def test_pipeline_only_observers_still_refuse_the_sync_driver(flag):
    from repro.launch import train

    with pytest.raises(SystemExit, match="add --pipeline"):
        train.main(["--arch", "paac_vector", "--iterations", "1"] + flag)
