"""Left, semi and anti joins the one-chip engine ran through `_device_probe`
per statement of the window (`JOIN_STATS`, the window's growth; Q13, Q22 and
Q4 plan one each, so every window reads 1.0: a run that reads less answered a
statement's join on the host path or down another one)."""

from benchmarks.harness import local_joins

SOURCE = "program_counter"
LAYER = "local executor"
MOVES = "ap_geomean_s"
UNIT = "1/stmt"


def read(run):
    return local_joins.per_statement(run, ("left", "semi", "anti"))
