"""95th percentile of the gap between a reply's arrival and the same client's
next send: how late the load generator itself ran."""

from benchmarks.harness import stats

SOURCE = "host_clock"
LAYER = "load generator"
MOVES = "tp_p95_ms"
UNIT = "ms"


def read(run):
    gaps = run.window.get("gaps_ms")
    return stats.percentile(gaps, 95.0) if gaps else None
