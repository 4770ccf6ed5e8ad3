"""Geometric mean, over the cell's queries, of each query's median wall time at
the client (statement sent to last result packet received), over every
execution of the window."""

from benchmarks.harness import stats

SOURCE = "host_clock"
LAYER = "end to end"
MOVES = None
UNIT = "s"


def read(run):
    lat = run.window.get("latencies_s")
    if not lat:
        return None
    return stats.geomean(stats.median(v) for v in lat.values())
