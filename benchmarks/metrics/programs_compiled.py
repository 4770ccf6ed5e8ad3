"""`COMPILE_STATS` retraces since process start: programs traced and compiled or
loaded from the persistent cache."""

SOURCE = "program_counter"
LAYER = "compile caches"
MOVES = "setup_s"
UNIT = "programs"


def read(run):
    return run.totals["programs_compiled"]
