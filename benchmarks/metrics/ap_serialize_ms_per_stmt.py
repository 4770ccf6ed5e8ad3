"""The program's `phase:serialize` spans (compaction of the last batch and its
rendering into rows) per traced statement.  `None` where the program entered
no such span."""

from benchmarks.harness import spans

SOURCE = "program_span"
LAYER = "statement pipeline and planner"
MOVES = "ap_geomean_s"
UNIT = "ms"


def read(run):
    s = spans.per_statement(run, "serialize_s")
    return None if s is None else 1e3 * s
