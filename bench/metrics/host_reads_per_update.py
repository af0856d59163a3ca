"""Layer ``host loop``: device-to-host transfers per update that the
driver's metric read makes, from the window's trace: the
``np.asarray(jax.Array)`` host events (one per device array brought to the
host) inside the program's ``metrics.read`` spans, over the window's
updates (``benchlib.scopes``). Reports nothing for a program without the
span. Moves timesteps_per_s."""
from benchlib import scopes

UNIT = "transfers"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "host loop"
MOVES = "timesteps_per_s"


def read(run):
    return scopes.host_reads_per_update(run)
