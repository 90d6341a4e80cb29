"""Small arithmetic shared by the systems and the readers."""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over ALL values given (no trimming)."""
    if not values:
        raise ValueError("percentile of nothing")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def union_seconds(intervals: Iterable[Tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total = 0.0
    end = -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def merge_intervals(intervals: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered_seconds(a: float, b: float,
                    merged: Sequence[Tuple[float, float]]) -> float:
    """How much of [a, b) the merged (disjoint, sorted) intervals cover."""
    total = 0.0
    for s, e in merged:
        if e <= a:
            continue
        if s >= b:
            break
        total += min(e, b) - max(s, a)
    return total
