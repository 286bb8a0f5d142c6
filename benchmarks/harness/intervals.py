"""Interval arithmetic for the trace reduction: union, intersection and
subtraction of half-open [start, end) spans on one clock. Pure Python on
sorted lists; a traced window holds at most a few hundred thousand events."""

from __future__ import annotations

from typing import Iterable, List, Tuple

Span = Tuple[float, float]


def union(spans: Iterable[Span]) -> List[Span]:
    """Disjoint, sorted spans covering exactly what ``spans`` cover."""
    out: List[Span] = []
    for s, e in sorted(x for x in spans if x[1] > x[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(spans: Iterable[Span]) -> float:
    """Covered length (of the union, so overlaps count once)."""
    return sum(e - s for s, e in union(spans))


def intersect(a: Iterable[Span], b: Iterable[Span]) -> List[Span]:
    """Spans covered by both ``a`` and ``b`` (each taken as its union)."""
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Iterable[Span], b: Iterable[Span]) -> List[Span]:
    """Spans covered by ``a`` and not by ``b``."""
    out = []
    b = union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(spans: Iterable[Span], lo: float, hi: float) -> List[Span]:
    """The idle spans of [lo, hi): what ``spans`` do not cover."""
    return subtract([(lo, hi)], spans)
