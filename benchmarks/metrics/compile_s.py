"""`COMPILE_STATS` compile_ms since process start: first-invocation wall time of
those programs (XLA compile, or the persistent cache's load)."""

SOURCE = "program_span"
LAYER = "compile caches"
MOVES = "setup_s"
UNIT = "s"


def read(run):
    return run.totals["compile_ms"] / 1e3
