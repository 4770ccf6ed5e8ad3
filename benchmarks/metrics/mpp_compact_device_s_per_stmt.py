"""Chip 0's self time in instructions built under `exchange/compact` (a join
side's live rows moved to the front of fewer slots), per traced statement
(`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return stages.per_statement(run, stages.COMPACT)
