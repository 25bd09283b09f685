"""Write path, full-compaction cell: sorts a merge was cut into,
`merge{tiles}` / `merge{merges}` over the window: the key-range stream tiles
of 131,072 padded rows that serve a rewrite of more rows than one tile."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "merge", "tiles", "merge", "merges")
