"""Merge dispatch: median over the window's operations of the reading thread's
time in the program's `gather` spans: the winners taken from every column."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "gather")
