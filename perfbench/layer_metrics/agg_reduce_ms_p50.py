"""Aggregation path: median over the window's operations of the length of
the reading thread's `agg.reduce` spans: every field of a merge reduced, a
call a field on the planned route (each `agg.field` inside it), the kernel
and the fields taken from its outputs on the fused route. 0 on a program
without the span; None on a trace without operations."""

import statistics

from program_spans import load


def _reduce_s(span) -> float:
    return sum((c.end - c.start) if c.name == "agg.reduce" else _reduce_s(c) for c in span.children)


def read(w):
    per_op = [_reduce_s(o) * 1e3 for o in load(w.trace.path).operations]
    return statistics.median(per_op) if per_op else None
