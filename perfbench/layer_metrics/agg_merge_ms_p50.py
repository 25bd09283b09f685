"""Aggregation path: median over the window's operations of the reading
thread's self time in `lanes.encode`, `lanes.compress`, `merge.dispatch` and
`merge.resolve` outside any `agg.reduce`: the key lanes, the sort and the
plan's download of the planned route (on the fused route the kernel runs
inside `agg.reduce`, and this is the lanes alone). 0 on a program without the
spans; None on a trace without operations."""

import statistics

from program_spans import load, matches

WANTED = ("lanes.encode", "lanes.compress", "merge.dispatch", "merge.resolve")


def _outside_reduce_s(span) -> float:
    total = 0.0
    for child in span.children:
        if child.name == "agg.reduce":
            continue
        if matches(child.name, WANTED):
            total += child.self_s
        total += _outside_reduce_s(child)
    return total


def read(w):
    per_op = [_outside_reduce_s(o) * 1e3 for o in load(w.trace.path).operations]
    return statistics.median(per_op) if per_op else None
