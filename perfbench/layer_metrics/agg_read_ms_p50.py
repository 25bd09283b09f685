"""Aggregation path: median over the window's operations of the reading
thread's self time in `decode.all` (the wait for the whole-file decodes on
the pool) and `read.join` (every column of the section's files joined): the
whole-batch read head. 0 on a program without the spans."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "decode.all", "read.join")
