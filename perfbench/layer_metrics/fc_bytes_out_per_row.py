"""Write path, full-compaction cell: bytes of the data files a rewrite wrote
over the rows in them, `compaction{bytes_out}` / `compaction{rows_out}` over
the window: the encode's compression is part of the result, and an encode
that got faster by compressing less shows here. 0 where the window rewrote
no row."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "compaction", "bytes_out", "compaction", "rows_out") or 0.0
