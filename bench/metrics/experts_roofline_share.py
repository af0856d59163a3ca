"""Layer ``moe``: the held experts' grouped matmuls against the chip's
bf16 peak. The work is the program's own count of assignments to held
experts in the learner's trajectory passes over the window, L
(``count.moe_held_learner``; ``benchlib.counters``), times one expert's
SwiGLU FLOPs (``benchlib.token_flops``) times 4 + 1/t_max: the learner's
pass three times (forward and backward; its rematerialised forward is not
model work), acting's forwards once (the same observations under the same
parameters: L again) and the bootstrap's once (one step's observations of
the t_max: L/t_max).
The time is the device self time under the ``moe.experts`` scope
(``benchlib.model_scopes``). Model work only, so never above 100%.
Reports nothing for a program without the scope or the counter. Moves
timesteps_per_s."""
from benchlib import chip, counters, model_scopes, token_flops

UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "moe"
MOVES = "timesteps_per_s"


def read(run):
    learner = counters.last_run("count.moe_held_learner")
    seconds = model_scopes.seconds_under(run, "moe.experts")
    if learner is None or not seconds:
        return None
    passes = 4.0 + 1.0 / run.workload["t_max"]
    flops = token_flops.expert_flops_per_assignment(run.config) * passes * learner
    peak = chip.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (seconds * run.chips * peak)
