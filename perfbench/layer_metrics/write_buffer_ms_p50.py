"""Write path: per client operation, the `write` spans (TableWrite.write: the
batch made columnar, routed to its buckets and appended to their memtables),
median over the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "write")
