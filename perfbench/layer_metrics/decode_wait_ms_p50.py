"""File decode: median over the window's operations of the time the reading
thread spends in the program's `decode.keys`, `decode.values` and `decode.all`
spans: its wait for the pool's decodes, and the decodes it runs itself."""

from program_spans import median_self_ms


def read(w):
    return median_self_ms(w, "decode")
