"""Write path, full-compaction cell: the benchmark's own `pb:clone` span (the
clone of the operation before dropped, a fresh hard-link clone of the base
made), on the host's clock: the benchmark's overhead inside an operation.
Median over the window's operations; 0 where the window's operations opened
none; nothing to read without an operation."""

import statistics


def read(w):
    clones = w.span_s("clone")
    if not clones:
        return 0.0 if w.span_s("op") else None
    return statistics.median(clones) * 1e3
