"""Merge dispatch: median over the window's operations of the reading thread's
time in the program's `merge.resolve` spans: blocked on the device, then the
download of the selection."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "merge.resolve")
