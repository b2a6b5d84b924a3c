"""Self time of one set of program spans: how much of their union the
spans of another set do not also cover."""

from benchmarks.trace_reduce import covered, union


def overlap(a, b) -> float:
    """Length of the intersection of the unions of intervals a and b."""
    ua, ub = union(a), union(b)
    total, j = 0.0, 0
    for s, e in ua:
        while j < len(ub) and ub[j][1] <= s:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            total += min(e, ub[k][1]) - max(s, ub[k][0])
            k += 1
    return total


def self_time(outer, inner) -> float:
    """Length of the union of outer less its overlap with inner."""
    return covered(outer) - overlap(outer, inner)
