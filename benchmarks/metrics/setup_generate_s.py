"""Seconds the deployment's generator took to make the cell's data from the
seed (the kind's `timings["generate_s"]`), a part of `setup_s`."""

SOURCE = "host_clock"
LAYER = "storage"
MOVES = "setup_s"
UNIT = "s"


def read(run):
    return getattr(run.deployment, "timings", {}).get("generate_s")
