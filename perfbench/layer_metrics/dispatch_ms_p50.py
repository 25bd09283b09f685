"""Merge dispatch: median over the window's operations of the reading thread's
self time in the program's `merge.dispatch` spans: tile boundaries, tile
gather, padding and the jitted call (lane compression inside it is
`lane_encode_ms_p50`'s)."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "merge.dispatch")
