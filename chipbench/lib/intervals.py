"""Interval arithmetic on a trace's events, in nanoseconds."""

from __future__ import annotations


def union(spans, lo: float, hi: float) -> list:
    """Sorted disjoint ``[start, end)`` pieces covering ``spans`` (pairs of
    start and end), clipped to ``[lo, hi)``."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(pieces) -> float:
    return sum(e - s for s, e in pieces)


def minus(a, b) -> list:
    """The parts of disjoint sorted pieces ``a`` not covered by disjoint
    sorted pieces ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def gaps(pieces, lo: float, hi: float) -> list:
    """The idle ``[start, end)`` stretches of ``[lo, hi)`` between pieces."""
    return minus([[lo, hi]], pieces)


def spans(events) -> list:
    """``(start, end)`` of ``[name, start, duration]`` events."""
    return [(s, s + d) for _, s, d in events]
