"""Merge dispatch: bytes fetched back from the merge kernels
(`merge{d2h_bytes}`) per row merged (`merge{rows_in}`) over the window."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "merge", "d2h_bytes", "merge", "rows_in")
