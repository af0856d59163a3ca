"""Layer ``learner``: device milliseconds per update of the update (the
program's ``learner`` scope: bootstrap forward, n-step returns, the loss's
forward and backward, the optimizer), from each op's self time in the
device trace, averaged over the cell's chips (``benchlib.scopes``).
Reports nothing for a program without the scope. Moves timesteps_per_s."""
from benchlib import scopes

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "learner"
MOVES = "timesteps_per_s"


def read(run):
    return scopes.layer_ms_per_update(run, "learner")
