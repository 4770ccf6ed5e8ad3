"""Seconds from the generated columns to loaded, analyzed and counted tables
(the kind's `timings["load_s"]`: DDL, the bulk insert, ANALYZE TABLE and a
COUNT(*) a table, the reference's frames where the kind builds them there), a
part of `setup_s`."""

SOURCE = "host_clock"
LAYER = "storage"
MOVES = "setup_s"
UNIT = "s"


def read(run):
    return getattr(run.deployment, "timings", {}).get("load_s")
