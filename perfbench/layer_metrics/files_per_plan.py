"""Planning: data files a plan resulted in, `scan{resulted_table_files}` /
`scan{plans}` over the window."""

from program_spans import counter_ratio


def read(w):
    return counter_ratio(w, "scan", "resulted_table_files", "scan", "plans")
