"""Device: memory_stats()["peak_bytes_in_use"] read once after the window,
the highest over the cell's devices."""


def read(w):
    return w.memory_peak_bytes or None
