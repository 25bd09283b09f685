"""Table API: median over the window's operations of the reading thread's self
time in the program's `concat` spans (the key-pass, value-pass, section and
split concatenations) and `finish` spans (drop deletes, predicate, projection)."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "concat", "finish")
