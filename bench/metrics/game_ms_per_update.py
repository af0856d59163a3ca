"""Layer ``game``: device milliseconds per update of the environment step
(the program's ``env.step`` scope: the game's dynamics, its auto-reset and
the frame stack), from each op's self time in the device trace, averaged
over the cell's chips. An op's scope comes from the compiled program's HLO
(``benchlib.scopes``). Reports nothing for a program without the scope.
Moves timesteps_per_s."""
from benchlib import scopes

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "game"
MOVES = "timesteps_per_s"


def read(run):
    return scopes.layer_ms_per_update(run, "game")
