"""Write path: per client operation, the `encode.arrow` spans inside each
`file.write` (the batch given its on-disk system columns, and made an arrow
table for the library), on whichever thread. Busy time. Median over the
window's operations; 0 on a program without the span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "encode.arrow")
