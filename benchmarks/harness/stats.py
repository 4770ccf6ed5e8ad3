"""Arithmetic every metric shares.  Kept with the benchmark: a PR that claims a
gain cannot change how a percentile or a mean is taken."""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear interpolation between the two
    closest ranks (numpy's default method)."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def geomean(values: Iterable[float]) -> float:
    vals = list(values)
    if not vals or any(v <= 0 for v in vals):
        raise ValueError(f"geometric mean needs positive values, got {vals}")
    return math.exp(sum(math.log(v) for v in vals) / len(vals))


def merge_intervals(intervals: Iterable[Sequence[float]]) -> List[List[float]]:
    """[start, end) intervals merged where they touch or overlap, in order."""
    out: List[List[float]] = []
    for s, e in sorted((float(a), float(b)) for a, b in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out
