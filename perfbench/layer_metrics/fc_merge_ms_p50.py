"""Write path, full-compaction cell: per client operation, the merge of the
rewrite up to the selection in hand: the spans `lanes.encode`,
`merge.dispatch` (any `lanes.compress` lies inside it and is counted with
it) and `merge.resolve`, summed an operation. Median over the window's
operations."""

import statistics

from ingest_spans import load

SPANS = ("lanes.encode", "merge.dispatch", "merge.resolve")


def read(w):
    spans = load(w.trace.path)
    per_op = [sum(parts) for parts in zip(*(spans.per_op_ms(name) for name in SPANS))]
    return statistics.median(per_op) if per_op else None
