"""Chip 0's self time in operations of the sort group's modules
(`jit_sort`, `jit_window` and the MPP window and top-n programs:
`harness/spans.py` FAMILY_GROUP), per traced statement."""

from benchmarks.harness import spans

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return spans.per_statement(run, "families", "sort")
