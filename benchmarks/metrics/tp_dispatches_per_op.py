"""`DISPATCH_STATS` delta over the window, per operation answered."""

SOURCE = "program_counter"
LAYER = "local executor"
MOVES = "tp_ops_per_s"
UNIT = "1/op"


def read(run):
    if "latencies_ms" not in run.window or not run.window["attempted"]:
        return None
    return run.counts["dispatches"] / run.window["attempted"]
