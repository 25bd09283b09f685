"""Kernels: device busy time (union of the device-op intervals of the trace,
summed over the devices) per million input rows of the window's operations."""


def read(w):
    busy = sum(w.busy_s.values())
    return busy * 1e3 / (w.rows / 1e6) if busy > 0 and w.rows else None
