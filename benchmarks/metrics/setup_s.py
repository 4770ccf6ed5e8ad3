"""Process start to window start: generate, load, ANALYZE, cache load or compile,
warm-up and its checks."""

SOURCE = "host_clock"
LAYER = "end to end"
MOVES = None
UNIT = "s"


def read(run):
    return run.setup_s
