"""Table API: rows returned / records read, from the program's `read{rows_out}`
and `read{rows_in}` over the window. A property of the data (the share of
records that are their key's last writer), which moves only if the merge or
the counter is wrong. The records the program counts for an operation must be
the cell's `rows`, the base of `rows_per_s`: anything else is an error."""

from program_spans import counter_delta, counter_ratio


def read(w):
    ops, rows_in = counter_delta(w, "read", "ops"), counter_delta(w, "read", "rows_in")
    if ops and rows_in != ops * w.rows_per_op:
        raise ValueError(f"read{{rows_in}} {rows_in} over {ops} operations is not the cell's {w.rows_per_op} rows an operation")
    return counter_ratio(w, "read", "rows_out", "read", "rows_in")
