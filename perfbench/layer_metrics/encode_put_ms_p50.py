"""Write path: per client operation, the `encode.put` spans inside each
`file.write` (the encoded bytes out of their buffer and onto the file system,
and the file's size read back), on whichever thread. Busy time. Median over
the window's operations; 0 on a program without the span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "encode.put")
