"""Chip 0's self time in operations of the scan group's modules
(`jit_filter`, `jit_project`, fused segments, `jit_batch_point`
and the MPP filter, project and concat programs:
`harness/spans.py` FAMILY_GROUP), per traced statement."""

from benchmarks.harness import spans

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "s"


def read(run):
    return spans.per_statement(run, "families", "scan")
