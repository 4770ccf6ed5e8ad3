"""Median operation latency at the client.  In a closed loop it is the client
count over tp_ops_per_s, so it is a per-layer reading, not a bound metric."""

from benchmarks.harness import stats

SOURCE = "host_clock"
LAYER = "statement pipeline"
MOVES = "tp_p95_ms"
UNIT = "ms"


def read(run):
    lat = run.window.get("latencies_ms")
    return stats.median(lat) if lat else None
