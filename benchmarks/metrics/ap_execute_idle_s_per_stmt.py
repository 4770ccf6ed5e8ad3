"""Seconds inside the program's `phase:execute` spans in which chip 0 ran
nothing, per traced statement: launch gaps and host syncs between programs.
`None` where the program entered no such span."""

from benchmarks.harness import spans

SOURCE = "device_trace"
LAYER = "local executor"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return spans.per_statement(run, "execute_idle_s")
