"""`SHOW BATCH STATS` group_size_p50: point statements served by one flush."""

SOURCE = "program_counter"
LAYER = "batch scheduler"
MOVES = "tp_ops_per_s"
UNIT = "statements"


def read(run):
    return run.window.get("batch_stats", {}).get("group_size_p50")
