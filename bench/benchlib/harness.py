"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order, in one process:

1. Set-up. Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
   (or where ``JAX_COMPILATION_CACHE_DIR`` says) with no minimum compile
   time. Require the cell's chips. Build the cell's entry from the seed
   and hold what the program will run against the configuration file.
   Drive the entry through its first three updates, one ``run(1)`` call
   each, keeping the losses, the optimizer's state after the first and the
   parameters before and after. Then size the window: time a ``run(k)``
   call, and from its rate choose n so that ``run(n)`` lasts about
   ``--seconds`` (or, traced, the traced window's length).
2. The window: one ``run(n)`` call on the same object, on the host clock,
   with nothing added inside it. ``setup_s`` is the time from the process's
   start to the window's start. With ``--trace 1`` the window runs under
   the JAX profiler inside a ``bench.window`` annotation.
3. After the window: read the chips' peak memory, free the program's state,
   run the configuration's family's plain reference over the same three
   updates and compare (see ``benchlib.check``). Then each metric's reader;
   ``mfu`` counts its FLOPs by the family.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key. Without the cell's chips the run exits 2
and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

from benchlib import cells, chip, check
from benchlib import trace as tr

CHECKED_UPDATES = 3
# the traced window is at most this long: a trace grows with every update
TRACE_SECONDS = 4.0
# the rate the window is sized from is timed over at least this long
CALIBRATE_SECONDS = 0.5


def use_compile_cache() -> str:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(cells.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts JAX's trace and backend-compile events while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


def _host(tree):
    import jax
    import numpy as np

    return jax.tree_util.tree_map(lambda x: np.array(x), tree)


def checked_updates(entry, steps: int = CHECKED_UPDATES) -> dict:
    """Drive ``entry`` through its first ``steps`` updates, one ``run(1)``
    each, and keep what the comparison needs."""
    params0 = _host(entry.params())
    losses, sq1 = [], None
    for i in range(steps):
        losses.append(float(entry.run(1).mean_metrics["loss"]))
        if i == 0:
            sq1 = _host(entry.opt_state()["sq"])
    return {"losses": losses, "sq1": sq1, "params0": params0,
            "params": _host(entry.params())}


def guard_widths(settings: dict, config: dict) -> None:
    """The program must run what the configuration file states."""
    wrong = {k: (v, config.get(k)) for k, v in settings.items()
             if config.get(k) != v}
    if wrong:
        raise ValueError("the program does not run the configuration file: "
                         + ", ".join(f"{k}: program {p!r}, file {f!r}"
                                     for k, (p, f) in sorted(wrong.items())))


def check_lr_rule(workload: dict, config: dict) -> None:
    want = config["lr_per_env"] * workload["n_envs"] * workload.get("lanes", 1)
    if not math.isclose(workload["lr"], want, rel_tol=1e-9):
        raise ValueError(f"lr {workload['lr']} is not lr_per_env * n_e = {want}")


def size_window(entry, seconds: float) -> int:
    """Updates that make one ``run(n)`` last about ``seconds``."""
    k = 4
    while True:
        t0 = time.perf_counter()
        entry.run(k)
        dt = time.perf_counter() - t0
        if dt >= CALIBRATE_SECONDS or k >= 1 << 16:
            return max(1, round(seconds * k / dt))
        k *= 4


def reference_readings(cell, seed: int, prog: dict, dtype=None,
                       fault: Optional[str] = None) -> Dict[str, float]:
    """The three numbers, the program's ``prog`` against the reference (or,
    with ``dtype``/``fault``, against a control or a planted fault)."""
    import jax.numpy as jnp

    ref = cell.family.train(cell.config, seed, steps=len(prog["losses"]),
                            dtype=dtype or jnp.float32, fault=fault,
                            **cell.entry.reference_layout(cell.workload))
    return check.readings(prog, ref, cell.config["optimizer"]["decay"])


def reference_as_program(cell, seed: int, dtype=None,
                         fault: Optional[str] = None) -> dict:
    """The reference put in the program's place: what ``checked_updates``
    would have read from it."""
    import jax
    import jax.numpy as jnp

    run = cell.family.train(cell.config, seed, dtype=dtype or jnp.float32,
                            fault=fault,
                            **cell.entry.reference_layout(cell.workload))
    decay = cell.config["optimizer"]["decay"]
    sq1 = jax.tree_util.tree_map(
        lambda g: (1.0 - decay) * jnp.square(g.astype(jnp.float32)),
        run["grads"])
    return {"losses": run["losses"], "sq1": _host(sq1),
            "params0": _host(run["params0"]), "params": _host(run["params"])}


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    return opts


def run_cell(cell, seed: int, seconds: float, traced: bool, devices,
             t_start: float, metric_names: List[str],
             keep_trace: Optional[str] = None,
             plant: Optional[Callable] = None) -> dict:
    """Set-up, window, comparison and metrics of one run; returns the
    result object. ``plant``, where given, is called on the built entry
    before anything runs (the fault tests break the program with it)."""
    import jax

    workload, config = cell.workload, cell.config
    check_lr_rule(workload, config)
    counter = CompileCounter()
    entry = cell.entry.Entry(config, workload, seed, devices)
    guard_widths(entry.settings, config)
    if plant is not None:
        plant(entry)
    prog = checked_updates(entry)
    window_target = min(seconds, TRACE_SECONDS) if traced else seconds
    n = size_window(entry, window_target)

    trace_dir = None
    counter.on = True
    if traced:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                t0 = time.perf_counter()
                res = entry.run(n)
                t1 = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
    else:
        t0 = time.perf_counter()
        res = entry.run(n)
        t1 = time.perf_counter()
    counter.on = False
    setup_s = t0 - t_start
    window_s = t1 - t0
    print(f"window: {n} updates in {window_s:.4f} s, {counter.count} "
          "compile events inside it", file=sys.stderr)
    mem_peak = chip.memory_peak_bytes(devices)
    timesteps = n * entry.timesteps_per_update
    window_loss = res.mean_metrics.get("loss", math.nan)
    learner_idle_s = res.learner_idle_s
    entry.close()
    del entry, res
    gc.collect()

    numbers = reference_readings(cell, seed, prog)
    limits = workload["limits"]
    correct = check.verdict(numbers, limits) and math.isfinite(window_loss)

    device = chip.describe(devices)
    device["memory_peak_bytes"] = mem_peak
    ctx = SimpleNamespace(
        setup_s=setup_s, window_s=window_s, updates=n, timesteps=timesteps,
        learner_idle_s=learner_idle_s, chips=len(devices), config=config,
        workload=workload, device_kind=devices[0].device_kind,
        flops_per_timestep=cell.family.flops_per_timestep(
            config, workload["t_max"]),
        learner_queue=getattr(cell.entry, "LEARNER_QUEUE", False),
        trace=None, lo=None, hi=None, busy_s=None, trace_window_s=None)
    breakdown = None
    if traced:
        try:
            trace = tr.load(tr.find_xplane(trace_dir))
        finally:
            if keep_trace is None:
                shutil.rmtree(trace_dir, ignore_errors=True)
        used = {d.id for d in devices}
        trace = trace._replace(devices={k: v for k, v in trace.devices.items()
                                        if k in used})
        window = tr.span(trace, "bench.window")
        if window is None:
            raise RuntimeError("the trace has no bench.window span")
        lo, hi = window
        busy = [tr.busy_ns(ev, lo, hi) * 1e-9 for ev in trace.devices.values()]
        if not busy or min(busy) <= 0:
            raise RuntimeError(f"the trace shows no device op on a chip in the "
                               f"window: chips {sorted(trace.devices)}")
        ctx.trace, ctx.lo, ctx.hi = trace, lo, hi
        ctx.busy_s = sum(busy) / len(busy)
        ctx.trace_window_s = (hi - lo) * 1e-9
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.trace_window_s
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(trace, lo, hi)],
                     "idle_gaps": [list(x) for x in tr.longest_gaps(
                         trace, lo, hi, skip=("bench.window",))]}

    metrics = {}
    for name in metric_names:
        reader = cells.reader(name)
        value = reader.read(ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    result = {"correct": bool(correct), "attempted": n,
              "failed": 0 if math.isfinite(window_loss) else n,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": numbers[k], "limit": limits[k]}
                       for k in check.NUMBERS}
    return result


def _finite(x):
    """JSON has no inf or nan: such a number is written as its name."""
    if isinstance(x, float) and not math.isfinite(x):
        return repr(x)
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_finite(v) for v in x]
    return x


def parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="keep the traced window's profile in this directory")
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    try:
        bench = cells.spec()
        cell = cells.load_cell(args.workload)
        names = cells.metrics_for(bench, args.workload, bool(args.trace))
        print(f"compile cache: {use_compile_cache()}", file=sys.stderr)
        devices = chip.require(cell.workload["chips"])
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, t_start, names, keep_trace=args.keep_trace)
    except chip.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    for k, v in result["check"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_finite(result)), flush=True)
    return 0
