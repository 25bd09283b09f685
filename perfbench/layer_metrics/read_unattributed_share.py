"""Table API: part of the window's `read_all` time that lies in a container span
(`read_all`, `split`) and in no child of it: work the program has not named."""

from program_spans import load


def read(w):
    return load(w.trace.path).unattributed_share()
