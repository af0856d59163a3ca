"""What the program itself marks in a trace: its named scopes on the device
and its host loop's spans.

Device side. The train step names its parts with ``jax.named_scope``:
``rollout`` around the acting scan, ``policy.forward`` and ``env.step``
inside its body, ``learner`` around the update, ``loss_grad`` and
``optimizer`` inside it. XLA keeps each instruction's scope path in its
``op_name`` metadata (``jit(train_step)/rollout/while/body/env.step/...``;
the backward pass wraps a path as ``transpose(jvp(...))``). A trace's
device ops carry only their instruction name, so an op's scope is looked
up in the compiled program's HLO by that name (``op_names``). An op with
no scope there takes the scope of the innermost op that encloses it in
time on the same device line: the ``while`` loop it runs in. Each op's
self time (``trace.self_times``) goes to one layer, or to none, so the
layers and the unscoped rest add up to the busy time exactly.

Before any op is put in a layer, the map is checked against the trace:
where more than ``MISMATCH`` of the window's self time falls on op names
the compiled program does not have, the map is not the program the window
ran, and the layer readers report nothing (and say so on stderr).

Host side. ``ParallelRL.run`` records ``step.dispatch`` and
``metrics.read`` spans, which the profiler keeps in its host plane on the
same clock as the device's ops; the device's idle time that each covers
is read against the device's gaps. Inside ``metrics.read`` the driver
reads each device metric scalar once, and JAX marks each such transfer
with one ``np.asarray(jax.Array)`` host event: their count over the
window's updates is the transfers per update.

A program without the scopes or spans (an older one) reads as nothing:
the readers return ``None``.
"""
from __future__ import annotations

import re
import sys
import traceback
from types import SimpleNamespace
from typing import Dict, Iterable, List, Optional, Tuple

from benchlib import cells
from benchlib import trace as tr

SCOPES = ("rollout", "policy.forward", "env.step", "learner", "loss_grad",
          "optimizer")
# layer of an op: its innermost scope that names one, in this order
LAYERS = (("env.step", "game"), ("rollout", "acting"), ("learner", "learner"))
DISPATCH_SPAN = "step.dispatch"
READ_SPAN = "metrics.read"
# JAX's host event of one device array brought to the host
TRANSFER = "np.asarray(jax.Array)"
# share of the window's device self time on op names the scope map lacks
# above which the map is not the window's program
MISMATCH = 0.01

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+) .*\{\s*$")
_CALLS = re.compile(r"calls=(%[^\s,]+)")
_WRAPPER = re.compile(r"^(?:[\w.]+\()+")


def scopes_in(op_name: str) -> Tuple[str, ...]:
    """The program's scopes on an ``op_name`` path, outermost first. A
    fused op may carry several paths joined by ``;``: the first counts."""
    path = op_name.split(";", 1)[0]
    found = []
    for part in path.split("/"):
        part = _WRAPPER.sub("", part).rstrip(")")
        if part in SCOPES:
            found.append(part)
    return tuple(found)


def layer_of(op_name: Optional[str]) -> Optional[str]:
    """``game``, ``acting``, ``learner`` or ``None`` for one op path."""
    if not op_name:
        return None
    found = scopes_in(op_name)
    for scope, layer in LAYERS:
        if scope in found:
            return layer
    return None


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name (``%fusion.3``, as a trace names its op) ->
    ``op_name`` metadata (``""`` where it has none), from a compiled
    program's HLO text. Only the instructions that run as ops of their
    own: those inside a fusion's computation never show in a trace, and
    leaving them out keeps their names from matching another program's
    ops."""
    by_computation: Dict[str, Dict[str, str]] = {}
    fused = set()
    current: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = by_computation.setdefault(head.group(1), {})
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op_name = _OP_NAME.search(line)
            current[m.group(1)] = op_name.group(1) if op_name else ""
        if " fusion(" in line:
            fused.update(_CALLS.findall(line))
    out: Dict[str, str] = {}
    for name, instructions in by_computation.items():
        if name not in fused:
            out.update(instructions)
    return out


def _enclosing(events: List[tr.Event], i: int,
               longest: float) -> Iterable[tr.Event]:
    """The events of one line that enclose ``events[i]`` in time,
    innermost (latest start) first; ``events`` sorted by start, none
    longer than ``longest``."""
    ev = events[i]
    for j in range(i - 1, -1, -1):
        other = events[j]
        if other.start < ev.end - longest:
            return
        if other.end >= ev.end:
            yield other


def op_layers(events: List[tr.Event], op_names: Dict[str, str],
              lo: float, hi: float) -> Dict[str, Optional[str]]:
    """Layer of every op name on one device line inside [lo, hi): its own
    scope, else that of the innermost enclosing op that has one (found at
    the op's first run in the window; an instruction sits in one place of
    the program, so every run of it has the same enclosing ops)."""
    layers: Dict[str, Optional[str]] = {}
    longest = max((e.end - e.start for e in events), default=0.0)
    for i, ev in enumerate(events):
        if ev.name in layers or ev.end <= lo or ev.start >= hi:
            continue
        layer = layer_of(op_names.get(ev.name))
        if layer is None:
            for outer in _enclosing(events, i, longest):
                layer = layer_of(op_names.get(outer.name))
                if layer is not None:
                    break
        layers[ev.name] = layer
    return layers


def layer_ns(events: List[tr.Event], op_names: Dict[str, str], lo: float,
             hi: float, times: Optional[Dict[str, float]] = None
             ) -> Dict[Optional[str], float]:
    """Self time inside [lo, hi) per layer (``None``: unscoped) of one
    device line (``times``: its ``trace.self_times``, where already
    known); the values add up to ``trace.busy_ns`` exactly."""
    layers = op_layers(events, op_names, lo, hi)
    if times is None:
        times = tr.self_times(events, lo, hi)
    out: Dict[Optional[str], float] = {}
    for name, ns in times.items():
        layer = layers.get(name)
        out[layer] = out.get(layer, 0.0) + ns
    return out


def covered_ns(gaps: List[Tuple[float, float]],
               cover: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, j = 0.0, 0
    for s, e in gaps:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return total


def idle_covered_ms_per_update(run, span_name: str) -> Optional[float]:
    """Device idle milliseconds per update inside host spans called
    ``span_name``, averaged over the chips; ``None`` where the trace holds
    no such span."""
    if run.trace is None or not run.trace.devices:
        return None
    spans = [e for e in run.trace.host if e.name == span_name]
    if not spans:
        return None
    cover = tr.merged(spans, run.lo, run.hi)
    per_chip = [covered_ns(tr.gaps(ev, run.lo, run.hi), cover)
                for ev in run.trace.devices.values()]
    return 1e-6 * sum(per_chip) / len(per_chip) / run.updates


def host_reads_per_update(run) -> Optional[float]:
    """Device-to-host transfers per update: the ``np.asarray(jax.Array)``
    host events that start inside a ``metrics.read`` span of the window,
    over the window's updates; ``None`` where the trace holds no such
    span."""
    if run.trace is None:
        return None
    reads = tr.merged((e for e in run.trace.host if e.name == READ_SPAN),
                      run.lo, run.hi)
    if not reads:
        return None
    count, j = 0, 0
    for ev in run.trace.host:  # by start
        if ev.name != TRANSFER or not run.lo <= ev.start < run.hi:
            continue
        while j < len(reads) and reads[j][1] <= ev.start:
            j += 1
        count += j < len(reads) and reads[j][0] <= ev.start
    return count / run.updates


def probe(run) -> SimpleNamespace:
    """The scope path of each instruction of the cell's compiled train
    step, once per run. Read from the cell's entry rebuilt after the
    window, from the seed 0 (the harness hands readers no program; the
    program does not depend on the seed, and the compile cache answers
    with the executable the window ran). Empty where the program offers no
    compiled step (an entry without ``rl.compiled``); a probe that fails
    says so on stderr and reads as empty."""
    found = getattr(run, "program_probe", None)
    if found is not None:
        return found
    found = run.program_probe = SimpleNamespace(op_names={})
    try:
        import jax

        module = cells.load_module(
            cells.BENCH / "entries" / f"{run.workload['entry']}.py")
        entry = module.Entry(run.config, run.workload, 0,
                             jax.devices()[:run.chips])
        try:
            compiled = getattr(getattr(entry, "rl", None), "compiled", None)
            if compiled is not None:
                found.op_names = hlo_op_names(compiled().as_text())
        finally:
            entry.close()
    except Exception:  # a reader must not fail the run: report, read nothing
        print("bench: reader failed: scopes.probe could not read the "
              "program's scope map; the layer metrics are left out:",
              file=sys.stderr)
        traceback.print_exc()
    return found


def unmapped_share(times: Dict[str, float], op_names: Dict[str, str]
                   ) -> float:
    """Share of a device line's self time (``trace.self_times``) on op
    names that ``op_names`` lacks."""
    total = sum(times.values())
    missing = sum(ns for name, ns in times.items() if name not in op_names)
    return missing / total if total > 0 else 0.0


def layer_split(run) -> Dict[Optional[str], float]:
    """Device self milliseconds per update of each layer (``None``: the
    unscoped rest), averaged over the chips; once per run. Empty where no
    op of the program carries a layer's scope, or where the scope map does
    not name the window's ops (more than ``MISMATCH`` of the self time on
    names it lacks)."""
    split = getattr(run, "layer_split", None)
    if split is not None:
        return split
    split = run.layer_split = {}
    if run.trace is None or not run.trace.devices:
        return split
    names = probe(run).op_names
    if not any(layer_of(p) for p in names.values()):
        return split
    lines = sorted(run.trace.devices.items())
    times = [tr.self_times(ev, run.lo, run.hi) for _, ev in lines]
    for (dev, _), t in zip(lines, times):
        share = unmapped_share(t, names)
        if share > MISMATCH:
            print(f"bench: reader failed: the scope map lacks the op names "
                  f"of {share:.2%} of chip {dev}'s self time; the layer "
                  "metrics are left out", file=sys.stderr)
            return split
    for (_, ev), t in zip(lines, times):
        for key, ns in layer_ns(ev, names, run.lo, run.hi, t).items():
            split[key] = split.get(key, 0.0) + ns
    scale = 1e-6 / len(run.trace.devices) / run.updates
    for key in split:
        split[key] *= scale
    return split


def layer_ms_per_update(run, layer: str) -> Optional[float]:
    """One layer's share of ``layer_split``; ``None`` where it is empty."""
    split = layer_split(run)
    if not split:
        return None
    return split.get(layer, 0.0)
