"""Summed durations of all-to-all / all-gather / all-reduce / collective-permute
operations on chip 0, per traced statement."""

SOURCE = "device_trace"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    traced = run.window.get("traced")
    if run.trace is None or not traced or not traced["statements"]:
        return None
    return run.trace["collective_s_chip0"] / traced["statements"]
