"""End to end: environment timesteps learned from per second, over all the
work and all the time of the measured window (the paper's Fig. 2/4 metric).
"""
UNIT = "timesteps/s"
BETTER = "higher"
SOURCE = "host_clock"


def read(run):
    return run.timesteps / run.window_s
