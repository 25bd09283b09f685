"""File decode: median over the window's operations of the summed length of the
operation's `decode.file` spans on every thread (joined by their `op`): the
decode work itself, where `decode_wait_ms_p50` is how long the reader waited
for it. 0 where every read hit the data-file cache."""

from program_spans import median_busy_ms


def read(w):
    return median_busy_ms(w, "decode.file")
