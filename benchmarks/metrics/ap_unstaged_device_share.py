"""Share of chip 0's busy time in instructions of matched modules that sit
under no stage (`<family>/-`: a program of one stage, such as `bloom_query`,
or the part of one no `jax.named_scope` names) (`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    return stages.unstaged_share(run)
