"""Chip 0's self time in instructions built under `groupby/reduce` (running
sums and segmented scans read at the runs' ends), per traced statement
(`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return stages.per_statement(run, stages.GROUPBY_REDUCE)
