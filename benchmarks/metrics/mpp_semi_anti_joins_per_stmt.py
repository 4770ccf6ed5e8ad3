"""Semi and anti joins the MPP executor ran per statement the driver sent
(`MPP_JOIN_STATS`, cumulative; Q4 plans one and Q21 two, so whole rounds read
1.5: a run that reads less answered a statement down another path)."""

from benchmarks.harness import mesh, mpp_joins

SOURCE = "program_counter"
LAYER = "MPP executor"
MOVES = "ap_geomean_s"
UNIT = "1/stmt"


def read(run):
    stats, sent = mpp_joins.join_stats(), mesh.statements_sent(run)
    if stats is None or not sent:
        return None
    return mpp_joins.joins_of_kinds(stats, ("semi", "anti")) / sent
