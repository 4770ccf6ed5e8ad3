"""Median wall time of Q13 at the client over the window: which query carries a
change or a spread of ap_geomean_s."""

from benchmarks.harness import stats

SOURCE = "host_clock"
LAYER = "local executor"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    lat = run.window.get("latencies_s", {}).get("q13")
    return stats.median(lat) if lat else None
