"""Median `QueryProfile.elapsed_ms` of the last statements of the window (the
profile ring keeps 256): what the server itself spent on a statement."""

from benchmarks.harness import stats

SOURCE = "program_span"
LAYER = "statement pipeline"
MOVES = "tp_p95_ms"
UNIT = "ms"


def read(run):
    elapsed = run.window.get("server_elapsed_ms")
    return stats.median(elapsed) if elapsed else None
