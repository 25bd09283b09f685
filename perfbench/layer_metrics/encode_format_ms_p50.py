"""Write path: per client operation, the `encode.format` spans inside each
`file.write` (the library's encode of the whole file into a buffer, or the
native parquet encoder), on whichever thread. Busy time. Median over the
window's operations; 0 on a program without the span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "encode.format")
