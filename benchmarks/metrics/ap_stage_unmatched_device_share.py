"""Share of chip 0's busy time in modules that no entry of the program's
registry matched, or that two entries split differently: seconds the stage
metrics leave out and never guess (`harness/stages.py`)."""

from benchmarks.harness import stages

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    return stages.unmatched_share(run)
