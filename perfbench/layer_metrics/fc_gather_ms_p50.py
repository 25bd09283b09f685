"""Write path, full-compaction cell: per client operation, the `gather` spans
(`MergeExecutor.gather`: the winners taken from every column of the whole
batch, on one thread). Median over the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "gather")
