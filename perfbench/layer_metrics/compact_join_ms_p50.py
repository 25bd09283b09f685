"""Write path: per client operation, the `compact.join` spans (inside a
rewrite's read head, `compact.read`: the decoded parts of a section joined
into the one batch the merge takes, every column, `seq` and `kind` copied
once more). What is left of `fc_read_ms_p50` is the fan-out and the wait for
the decodes. Median over the window's operations; 0 on a program without the
span."""

from ingest_spans import median_ms


def read(w):
    return median_ms(w, "compact.join")
