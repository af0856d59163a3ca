"""The program's run counters, as JAX's monitoring hands them over.

``ParallelRL.run`` publishes the run total of each counter its train step
carries (metrics named ``count.*``) as the scalar event
``/repro/run/<name>`` when a run ends. ``listen`` (called as a family
module is loaded, before any run) keeps the last value of each such event
in this process; a reader asks for the window's, which is the last run's.
A program that publishes none reads as ``None``.
"""
from __future__ import annotations

from typing import Dict, Optional

RUN_EVENT = "/repro/run/"
_last: Dict[str, float] = {}
_listening = False


def _seen(event: str, value, **_kw) -> None:
    if event.startswith(RUN_EVENT):
        _last[event[len(RUN_EVENT):]] = float(value)


def listen() -> None:
    """Start keeping the program's run counters (once per process)."""
    global _listening
    if not _listening:
        import jax

        jax.monitoring.register_scalar_listener(_seen)
        _listening = True


def last_run(name: str) -> Optional[float]:
    """Total of counter ``name`` (``count.moe_held_learner``, say) over the
    last run that published it; ``None`` where no run did."""
    return _last.get(name)
