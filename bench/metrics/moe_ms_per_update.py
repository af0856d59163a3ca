"""Layer ``moe``: device milliseconds per update in the expert layers (the
model's ``moe.route``, ``moe.experts``, ``moe.combine`` and ``moe.shared``
scopes, in acting, the bootstrap and the learner's pass with its
backward), from each op's self time in the device trace, averaged over
the cell's chips (``benchlib.model_scopes``). Reports nothing for a
program without the scopes. Moves timesteps_per_s."""
from benchlib import model_scopes

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "moe"
MOVES = "timesteps_per_s"


def read(run):
    return model_scopes.ms_per_update(run, "moe")
