"""File decode: per client operation, the `decode.convert` spans (a decoded
arrow table made Columns inside a `decode.file`: `ColumnBatch.from_arrow`'s
joins of chunks, copies to numpy, null fills and casts), on whichever thread.
Busy time. Median over the window's operations; 0 on a program without the
span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "decode.convert")
