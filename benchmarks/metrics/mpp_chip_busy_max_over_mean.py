"""Busy seconds of the busiest chip over the mean of the chips, in the traced
window: 1.0 is an even mesh, the chip count one chip doing everything."""

from benchmarks.harness import mesh

SOURCE = "device_trace"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "ratio"


def read(run):
    got = mesh.of_run(run)
    return None if got is None else mesh.busy_max_over_mean(got["busy_s"])
