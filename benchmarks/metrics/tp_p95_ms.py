"""95th percentile of an operation's latency at the client, over every operation
of the window; a failed or refused one counts as a miss (the window's length)."""

from benchmarks.harness import stats

SOURCE = "host_clock"
LAYER = "end to end"
MOVES = None
UNIT = "ms"


def read(run):
    lat = run.window.get("latencies_ms")
    if not lat:
        return None
    miss = run.window["window_s"] * 1e3
    return stats.percentile(lat + [miss] * run.window["failed"], 95.0)
