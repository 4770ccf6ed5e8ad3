"""Chip 0's self time in instructions built under `exchange/repartition` (a
shard's rows ordered by destination, scattered into quota slots and handed
to `all_to_all`), per traced statement (`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return stages.per_statement(run, stages.REPARTITION)
