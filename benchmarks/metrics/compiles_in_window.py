"""`COMPILE_STATS` retraces inside the measured window; a run with any is not
`correct`."""

SOURCE = "program_counter"
LAYER = "compile caches"
MOVES = "setup_s"
UNIT = "programs"


def read(run):
    return run.counts["programs_compiled"]
