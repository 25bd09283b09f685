"""Merge dispatch: bytes of the host arrays handed to the merge kernels
(`merge{h2d_bytes}`) per row merged (`merge{rows_in}`) over the window."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "merge", "h2d_bytes", "merge", "rows_in")
