"""Live rows over slots offered, all exchanges of all MPP statements so far
(`EXCHANGE_STATS`): how much of what the interconnect carried was rows."""

from benchmarks.harness import mesh

SOURCE = "program_counter"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    stats = mesh.exchange_stats()
    if stats is None or not stats.get("slots_offered"):
        return None
    return 100.0 * stats["live_rows"] / stats["slots_offered"]
