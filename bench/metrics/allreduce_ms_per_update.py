"""Layer ``sharded learner collectives``: milliseconds per learner update
in which an all-reduce ran on a chip, averaged over the cell's chips, from
the device trace. Reports nothing where the trace holds no all-reduce.
Moves timesteps_per_s."""
from benchlib import trace

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "sharded learner collectives"
MOVES = "timesteps_per_s"


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    per_chip = [trace.matching_ns(ev, run.lo, run.hi)
                for ev in run.trace.devices.values()]
    if not any(per_chip):
        return None
    return 1e-6 * sum(per_chip) / len(per_chip) / run.updates
