"""Bytes one shard handed to `all_to_all` and `all_gather` per statement: the
program's cumulative `EXCHANGE_STATS` over the statements the driver sent."""

from benchmarks.harness import mesh

SOURCE = "program_counter"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "bytes/stmt"


def read(run):
    stats, sent = mesh.exchange_stats(), mesh.statements_sent(run)
    if stats is None or not sent:
        return None
    return mesh.exchange_bytes_per_stmt(stats, sent)
