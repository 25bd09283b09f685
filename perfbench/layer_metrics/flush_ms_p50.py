"""Write path: per client operation, the `flush` spans (a memtable through the
merge: concat, key lanes, the device dedup, the gather, on the thread that
dispatches; then the level-0 files landed, on the flush worker), less the
`file.write` spans inside them, which `file_write_ms_p50` reads. Median over
the window's operations."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "flush", less=("file.write",))
