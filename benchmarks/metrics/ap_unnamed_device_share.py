"""Share of chip 0's busy time in operations of modules that belong to no family
of `harness/spans.py` FAMILY_GROUP (`jit_run`, an eager `jit_<primitive>`, a new
unnamed site) or of no module."""

from benchmarks.harness import spans

SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "ap_geomean_s"
UNIT = "%"


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    fam = got["families"]
    total = sum(fam[g] for g in spans.GROUPS) + fam["unnamed"]
    return 100.0 * fam["unnamed"] / total if total else None
