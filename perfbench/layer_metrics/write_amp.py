"""Write path: rows written to data files at any level over rows acknowledged:
(`flush{rows_out}` + `compaction{rows_out}`) / the window's input rows. A flush
writes one row a distinct key of its batch, a compaction round every row it
keeps; an upgrade writes nothing. Nothing to read on a program without the
`flush` group."""

from program_spans import counter_delta


def read(w):
    flushed = counter_delta(w, "flush", "rows_out")
    if flushed is None or not w.rows:
        return None
    return (flushed + (counter_delta(w, "compaction", "rows_out") or 0)) / w.rows
