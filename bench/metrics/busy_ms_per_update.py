"""Layer ``train step``: milliseconds in which an operation ran on a chip,
per learner update in the traced window, averaged over the cell's chips
(the fused collect and update on one chip; lane collect, learner step and
all-reduce on the mesh). Moves timesteps_per_s."""
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "train step"
MOVES = "timesteps_per_s"


def read(run):
    if run.trace is None or run.busy_s <= 0:
        return None
    return 1e3 * run.busy_s / run.updates
