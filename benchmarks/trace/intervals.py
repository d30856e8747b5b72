"""Interval arithmetic for the trace reduction. Pure functions over
``(start, end)`` pairs in one clock; no jax, no files."""

from __future__ import annotations


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: list[list[float]] = []
    for lo, hi in sorted((a, b) for a, b in intervals if b > a):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two interval sets (each is merged first)."""
    a, b = union(a), union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[float, float]]:
    """The part of ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for lo, hi in union(a):
        cur = lo
        for blo, bhi in b:
            if bhi <= cur:
                continue
            if blo >= hi:
                break
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out
