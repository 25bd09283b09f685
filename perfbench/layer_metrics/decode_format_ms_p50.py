"""File decode: per client operation, the `decode.format` spans (the format's
library at work inside a `decode.file`: the file opened and its footer parsed,
each stripe or run of row groups decoded, or the native decoder over the whole
file), on whichever thread. Busy time, as `decode_busy_ms_p50` is for the
files. Median over the window's operations; 0 on a program without the
span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "decode.format")
