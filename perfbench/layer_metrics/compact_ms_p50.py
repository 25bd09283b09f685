"""Write path: per client operation, the `compact` spans (a round of
trigger_compaction: the pick, the decode of the picked runs, their merge, the
gather), less the `file.write` spans inside them. Median over the window's
operations; a commit whose round picks nothing adds next to nothing."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "compact", less=("file.write",))
