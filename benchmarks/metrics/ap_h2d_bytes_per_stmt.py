"""`TRANSFER_STATS` bytes delta over the window, per statement; 0 when every
lane the statements read is resident."""

SOURCE = "program_counter"
LAYER = "lane caches"
MOVES = "ap_geomean_s"
UNIT = "bytes/stmt"


def read(run):
    if "latencies_s" not in run.window or not run.window["attempted"]:
        return None
    return run.counts["h2d_bytes"] / run.window["attempted"]
