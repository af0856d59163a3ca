"""Layer ``acting``: device milliseconds per update of the rollout less the
game (the program's ``rollout`` scope outside ``env.step``: the batched
policy forward, the categorical draw, the scan's keys and the trajectory
it stacks), from each op's self time in the device trace, averaged over
the cell's chips (``benchlib.scopes``). Reports nothing for a program
without the scope. Moves timesteps_per_s."""
from benchlib import scopes

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "acting"
MOVES = "timesteps_per_s"


def read(run):
    return scopes.layer_ms_per_update(run, "acting")
