"""Input rows (records in the files the plan reads, not winners) of all the
operations the window completed, over the window's whole length: t0 to the
return of the operation that was in flight at --seconds."""


def read(w):
    return w.rows / w.elapsed_s
