"""`SHOW BATCH STATS` wait_ms_p50: how long a statement waited for its group."""

SOURCE = "program_span"
LAYER = "batch scheduler"
MOVES = "tp_p95_ms"
UNIT = "ms"


def read(run):
    return run.window.get("batch_stats", {}).get("wait_ms_p50")
