"""Chip 0's self time in instructions built under `join_pairs/expand` (the
candidate ranges laid out as pair slots), per traced statement
(`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return stages.per_statement(run, stages.JOIN_EXPAND)
