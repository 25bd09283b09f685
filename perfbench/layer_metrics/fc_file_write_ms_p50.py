"""Write path, full-compaction cell: per client operation, every `file.write`
span (one output file encoded and written with its stats), on whichever
thread. Median over the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "file.write")
