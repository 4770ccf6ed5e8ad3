"""Union of device-operation intervals on chip 0 over the traced statements,
per statement."""

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    traced = run.window.get("traced")
    if run.trace is None or not traced or not traced["statements"]:
        return None
    return run.trace["busy_s_chip0"] / traced["statements"]
