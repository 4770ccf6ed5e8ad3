"""The least time the chip could take to read, once, the column lanes the traced
statements' plans read (their bytes over the chips' published HBM bytes/s), as
a share of the device time they took.  The bound is bytes: no operation count
is claimed for a relational plan."""

from benchmarks.harness.peaks import peaks_for

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    traced = run.window.get("traced")
    if run.trace is None or not traced or not traced["statements"]:
        return None
    peak = peaks_for(run.device["kind"])["hbm_bytes_per_s"]
    floor_s = traced["lane_bytes"] / (peak * run.trace["chips"])
    return 100.0 * floor_s / run.trace["busy_s_chip0"]
