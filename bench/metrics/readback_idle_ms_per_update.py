"""Layer ``host loop``: milliseconds per update in which the chip was idle
while the driver's host thread was inside a ``metrics.read`` span (the
host converting the update's metric scalars), from the program's spans in
the profile's host plane against the device trace's gaps, averaged over
the cell's chips. Reports nothing for a program without the span. Moves
timesteps_per_s."""
from benchlib import scopes

UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "host loop"
MOVES = "timesteps_per_s"


def read(run):
    return scopes.idle_covered_ms_per_update(run, scopes.READ_SPAN)
