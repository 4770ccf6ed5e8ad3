"""Operations answered correctly per second of window, all clients together."""

SOURCE = "host_clock"
LAYER = "end to end"
MOVES = None
UNIT = "ops/s"


def read(run):
    if "latencies_ms" not in run.window:
        return None
    return len(run.window["latencies_ms"]) / run.window["window_s"]
