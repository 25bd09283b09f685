"""Kernels: device busy time of the window outside the merge programs' sort
instructions (segment pass, selection pack, every other program), per million
input rows: `kernel_busy_ms_per_mrow` less `sort_busy_ms_per_mrow`."""

from program_spans import sort_busy_s


def read(w):
    sort = sort_busy_s(w)
    if sort is None or not w.rows:
        return None
    return (sum(w.busy_s.values()) - sort) * 1e3 / (w.rows / 1e6)
