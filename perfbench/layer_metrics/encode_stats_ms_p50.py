"""Write path: per client operation, the `encode.stats` spans inside each
`file.write` (what the file's manifest entry says of it: the index payload,
every column's min, max and null count, the key range, the delete count and
the sequence range), on whichever thread. Busy time. Median over the
window's operations; 0 on a program without the span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "encode.stats")
