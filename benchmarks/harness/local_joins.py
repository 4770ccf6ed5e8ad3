"""The one-chip engine's joins by kind: the program's `JOIN_STATS`
(`exec/operators.py`: equi-joins that went through `HashJoinOp._device_probe`
as `inner`, `left`, `semi`, `anti`, and `cap_climbs`, the runs of a pair
program that overflowed their capacity and ran again), read as it is.  On a
commit whose program keeps no such keys `join_stats` returns `None` and the
readers leave their metric out; nothing raises.

The counters are cumulative from process start and a first statement may
climb, so the readers take the window's two ends from the deployment kind
(`deployments/tpch_joinkinds.py` snapshots them where the driver snapshots the
engine's counters) and divide by the statements the window answered."""

from __future__ import annotations

from typing import Dict, Optional, Sequence

KEYS = ("inner", "left", "semi", "anti", "cap_climbs")


def join_stats() -> Optional[Dict[str, int]]:
    try:
        from galaxysql_tpu.exec import operators as ops
    except Exception:
        return None
    stats = getattr(ops, "JOIN_STATS", None)
    if not stats or any(k not in stats for k in KEYS):
        return None
    return {k: int(stats[k]) for k in KEYS}


def per_statement(run, keys: Sequence[str]) -> Optional[float]:
    """The window's growth of `keys`, summed, over the statements it answered."""
    before, after = getattr(run.deployment, "window_joins", (None, None))
    answered = run.window.get("attempted")
    if before is None or after is None or not answered:
        return None
    return sum(after[k] - before[k] for k in keys) / answered
