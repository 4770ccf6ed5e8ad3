"""Self time of the program's `phase:plan` spans (parse, bind, optimise, plan
cache; less any operator span nested in them) per traced statement.  `None`
where the program entered no such span."""

from benchmarks.harness import spans

SOURCE = "program_span"
LAYER = "statement pipeline and planner"
MOVES = "ap_geomean_s"
UNIT = "ms"


def read(run):
    s = spans.per_statement(run, "plan_self_s")
    return None if s is None else 1e3 * s
