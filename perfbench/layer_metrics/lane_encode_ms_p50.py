"""Key lanes: median over the window's operations of the reading thread's self
time in the program's `lanes.encode` and `lanes.compress` spans."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "lanes")
