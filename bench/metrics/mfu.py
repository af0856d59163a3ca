"""Layer ``model``: model FLOP utilisation of the whole step. The FLOPs the
network's forward and backward passes require per timestep (counted by the
configuration's family, ``families/<family>.py``; recomputation excluded),
times the timesteps of the traced window, over the traced window's length
times the chips times one chip's bf16 peak from ``bench/peaks.json``.
Moves timesteps_per_s."""
from benchlib import chip

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "timesteps_per_s"


def read(run):
    if run.trace is None:
        return None
    peak = chip.peaks(run.device_kind)["bf16_flops_per_s"]
    return (100.0 * run.flops_per_timestep * run.timesteps
            / (run.trace_window_s * run.chips * peak))
