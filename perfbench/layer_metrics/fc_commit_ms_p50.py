"""Write path, full-compaction cell: per client operation, the `commit` spans
(FileStoreCommit: manifests, manifest lists, the COMPACT snapshot's
compare-and-set). Median over the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "commit")
