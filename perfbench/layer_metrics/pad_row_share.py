"""Merge dispatch: rows sorted for nothing, `merge{pad_rows}` / (`merge{rows_in}`
+ `merge{pad_rows}`) over the window."""

from program_spans import counter_delta


def read(w):
    rows, pad = counter_delta(w, "merge", "rows_in"), counter_delta(w, "merge", "pad_rows")
    return pad / (rows + pad) if rows else None
