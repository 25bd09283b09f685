"""Write path, full-compaction cell: per client operation, the `compact.read`
spans (a rewrite's read head: the fan-out of the section's files over the
decode pool, the wait for them, and the concat of the decoded parts), on
whichever thread ran them. Median over the window's operations; 0 on a
program without the span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "compact.read")
