"""The part of chip 0's collective time during which chip 0 runs nothing else,
per traced statement (`harness/mesh.py:reduce_mesh`)."""

from benchmarks.harness import mesh

SOURCE = "device_trace"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    got, n = mesh.of_run(run), mesh.traced_statements(run)
    if got is None or not n:
        return None
    return got["collective_exposed_s"] / n
