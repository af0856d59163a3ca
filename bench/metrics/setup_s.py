"""End to end: seconds from the process's start to the measured window's
start: imports, the chip's start-up, building the job, compiling or loading
its programs from the cache, the three checked updates and the sizing of
the window."""
UNIT = "s"
BETTER = "lower"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
