"""Write path: per client operation, the `commit` spans (FileStoreCommit:
manifests, manifest lists, the snapshot, retries; two snapshots where the
commit carries a compaction). Median over the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "commit")
