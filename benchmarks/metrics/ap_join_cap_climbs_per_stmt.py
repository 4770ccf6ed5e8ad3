"""Runs of a join's pair program that overflowed their capacity and ran again,
per statement of the window (`JOIN_STATS["cap_climbs"]`, the window's growth):
0 once every capacity ladder has settled; a join that climbs in every
statement, or a seed that crosses a bucket's edge inside the window, shows
here."""

from benchmarks.harness import local_joins

SOURCE = "program_counter"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "1/stmt"


def read(run):
    return local_joins.per_statement(run, ("cap_climbs",))
