"""Layer ``mla``: device milliseconds per update in multi-head latent
attention (the model's ``mla`` scope: projections, rotary embedding,
attention, output projection; forward and backward), from each op's self
time in the device trace, averaged over the cell's chips
(``benchlib.model_scopes``). Reports nothing for a program without the
scope. Moves timesteps_per_s."""
from benchlib import model_scopes

UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "mla"
MOVES = "timesteps_per_s"


def read(run):
    return model_scopes.ms_per_update(run, "mla")
