"""The MPP executor's joins by kind: the program's `MPP_JOIN_STATS`
(`parallel/mpp.py`: equi-joins that ran to their end as `<kind>_<exchange>`,
and the live build rows its shuffles delivered through `all_to_all`), read as
it is.  On a commit whose program keeps no such counter `join_stats` returns
`None` and the readers leave their metric out; nothing raises."""

from __future__ import annotations

from typing import Dict, Optional


def join_stats() -> Optional[Dict[str, int]]:
    try:
        from galaxysql_tpu.parallel import mpp
    except Exception:
        return None
    stats = getattr(mpp, "MPP_JOIN_STATS", None)
    return dict(stats) if stats else None


def joins_of_kinds(stats: Dict[str, int], kinds) -> int:
    """Joins of the given plan kinds, whatever exchange each took."""
    return sum(n for key, n in stats.items()
               if key.rsplit("_", 1)[0] in kinds)
