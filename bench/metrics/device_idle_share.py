"""Layer ``device``: the share of the traced window in which no operation
ran on a chip, averaged over the cell's chips. Moves timesteps_per_s."""
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "timesteps_per_s"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace_window_s)
