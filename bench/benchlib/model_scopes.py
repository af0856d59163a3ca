"""The model's own name scopes inside the train step, as the device trace
shows them.

The token policies' blocks name their parts with ``jax.named_scope``:
``mla`` around multi-head latent attention, ``ffn`` around a dense
feed-forward, and in an expert layer ``moe.route`` (router, top-k, the
sort by expert), ``moe.experts`` (the held experts' grouped matmuls),
``moe.combine`` (weighting and summing each token's expert outputs) and
``moe.shared`` (the shared experts). They sit under the train step's own
scopes (``rollout``, ``learner``), so an op's path names one of each.

Each op of the window goes to its innermost model scope, looked up by its
instruction name in the compiled program's HLO (``scopes.probe``); an op
with none there takes that of the innermost op enclosing it in time on
its device line, as ``benchlib.scopes`` does for the train step's layers.
Where the scope map lacks the op names of more than ``scopes.MISMATCH``
of a chip's self time, or no op carries a model scope, the readers report
nothing.
"""
from __future__ import annotations

import sys
import traceback
from typing import Dict, Optional

from benchlib import scopes
from benchlib import trace as tr

MODEL_SCOPES = ("mla", "ffn", "moe.route", "moe.experts", "moe.combine",
                "moe.shared")


def model_scope(op_name: Optional[str]) -> Optional[str]:
    """The innermost model scope on an op's (first) path, or ``None``."""
    if not op_name:
        return None
    found = None
    for part in op_name.split(";", 1)[0].split("/"):
        part = scopes._WRAPPER.sub("", part).rstrip(")")
        if part in MODEL_SCOPES:
            found = part
    return found


def _op_scopes(events, op_names, lo, hi) -> Dict[str, Optional[str]]:
    out: Dict[str, Optional[str]] = {}
    longest = max((e.end - e.start for e in events), default=0.0)
    for i, ev in enumerate(events):
        if ev.name in out or ev.end <= lo or ev.start >= hi:
            continue
        scope = model_scope(op_names.get(ev.name))
        if scope is None:
            for outer in scopes._enclosing(events, i, longest):
                scope = model_scope(op_names.get(outer.name))
                if scope is not None:
                    break
        out[ev.name] = scope
    return out


def split_seconds(run) -> Dict[Optional[str], float]:
    """Device self seconds of the traced window per model scope (``None``:
    the rest), averaged over the chips; once per run. Empty where the
    scope map does not name the window's ops or no op has a model scope."""
    found = getattr(run, "model_split", None)
    if found is not None:
        return found
    found = run.model_split = {}
    try:
        _split(run, found)
    except Exception:  # a reader must not fail the run: report, read nothing
        print("bench: reader failed: the model scopes could not be read; "
              "their metrics are left out:", file=sys.stderr)
        traceback.print_exc()
        found.clear()
    return found


def _split(run, found: Dict[Optional[str], float]) -> None:
    if run.trace is None or not run.trace.devices:
        return
    names = scopes.probe(run).op_names
    if not any(model_scope(p) for p in names.values()):
        return
    lines = sorted(run.trace.devices.items())
    times = [tr.self_times(ev, run.lo, run.hi) for _, ev in lines]
    if any(scopes.unmapped_share(t, names) > scopes.MISMATCH for t in times):
        return
    for (_, ev), t in zip(lines, times):
        of = _op_scopes(ev, names, run.lo, run.hi)
        for name, ns in t.items():
            key = of.get(name)
            found[key] = found.get(key, 0.0) + ns * 1e-9 / len(lines)


def seconds_under(run, prefix: str) -> Optional[float]:
    """Window seconds under the model scopes named ``prefix`` or starting
    with ``prefix.``; ``None`` where the split is empty."""
    split = split_seconds(run)
    if not split:
        return None
    return sum(s for k, s in split.items()
               if k is not None and (k == prefix or k.startswith(prefix + ".")))


def ms_per_update(run, prefix: str) -> Optional[float]:
    s = seconds_under(run, prefix)
    return None if s is None else 1e3 * s / run.updates
