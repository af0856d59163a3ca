"""Layer ``pipeline learner loop``: the share of the traced window the
learner thread spent blocked on an empty trajectory queue, from the
pipeline's telemetry span totals (``RunResult.learner_idle_s``). Only
entries with a learner queue report it. Moves timesteps_per_s."""
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "pipeline learner loop"
MOVES = "timesteps_per_s"


def read(run):
    if not run.learner_queue:
        return None
    return 100.0 * run.learner_idle_s / run.window_s
