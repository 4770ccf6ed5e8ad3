"""Live build rows the shuffle joins' `all_to_all` delivered, over all shards,
per statement the driver sent (`MPP_JOIN_STATS["shuffle_build_rows"]`,
cumulative): whether a fact table really went through the exchange."""

from benchmarks.harness import mesh, mpp_joins

SOURCE = "program_counter"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "rows/stmt"


def read(run):
    stats, sent = mpp_joins.join_stats(), mesh.statements_sent(run)
    if stats is None or not sent:
        return None
    return stats["shuffle_build_rows"] / sent
