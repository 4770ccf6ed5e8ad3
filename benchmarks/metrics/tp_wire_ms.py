"""Median client latency minus median server statement time: socket, protocol,
the asyncio loop and the wait for an `exec` worker."""

from benchmarks.harness import stats

SOURCE = "host_clock"
LAYER = "wire"
MOVES = "tp_p95_ms"
UNIT = "ms"


def read(run):
    lat = run.window.get("latencies_ms")
    elapsed = run.window.get("server_elapsed_ms")
    if not lat or not elapsed:
        return None
    return stats.median(lat) - stats.median(elapsed)
