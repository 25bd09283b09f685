"""Table API: median wall time of one whole client operation in the window,
from the benchmark's own span around it. Against the untraced run's per-op
line it also gives what tracing costs."""

import statistics


def read(w):
    ops = w.span_s("op")
    return statistics.median(ops) * 1e3 if ops else None
