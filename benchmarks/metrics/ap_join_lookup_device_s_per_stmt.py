"""Chip 0's self time in instructions built under `join_pairs/sort`,
`join_pairs/probe` and `join_pairs/front` (the build side's order, the merged
range lookup, a mostly dead probe side moved to the front), per traced
statement; whichever program holds them, the mesh's join programs too
(`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return stages.per_statement(run, stages.JOIN_LOOKUP)
