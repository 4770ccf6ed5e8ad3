"""Client wall time minus device busy time, per traced statement: wire, parse,
plan, dispatch gaps and result rendering."""

SOURCE = "device_trace"
LAYER = "statement pipeline and planner"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    traced = run.window.get("traced")
    if run.trace is None or not traced or not traced["statements"]:
        return None
    return (traced["client_s"] - run.trace["busy_s_chip0"]) / traced["statements"]
