"""Write path: per client operation, every `file.write` span (one data file
encoded and written, under a flush or a compaction round, on whichever
thread). Median over the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "file.write")
