"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""

SOURCE = "device_trace"
LAYER = "device"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    if run.trace is None or "latencies_s" not in run.window:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
