"""`DISPATCH_STATS` delta over the window, per statement."""

SOURCE = "program_counter"
LAYER = "local executor"
MOVES = "ap_geomean_s"
UNIT = "1/stmt"


def read(run):
    if "latencies_s" not in run.window or not run.window["attempted"]:
        return None
    return run.counts["dispatches"] / run.window["attempted"]
