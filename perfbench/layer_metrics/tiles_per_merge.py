"""Merge dispatch: sorts a merge was cut into, `merge{tiles}` / `merge{merges}`
over the window."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "merge", "tiles", "merge", "merges")
